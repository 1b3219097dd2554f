"""Round program: device self seconds a traced round of the operations
whose framework name holds ``delta.inverse`` (the inverse of the
chunk's unit lower-triangular system inside ``lm.delta_rule``: the
substitution's steps in the forward pass and in the recomputation
under ``jax.checkpoint``, and the two products of its backward rule),
inside the round module's executions. ``round_delta_rule_device_s``
counts these seconds too; what that leaves is the batched products and
the two scans over chunks. None where the program carries no such
name. Source: device trace."""
from benchmark.harness import tag_reduce


def read(ctx):
    return tag_reduce.tagged_s_per_round(ctx, "delta.inverse")
