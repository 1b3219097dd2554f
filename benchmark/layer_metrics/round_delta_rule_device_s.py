"""Round program: device self seconds a traced round of the operations
under the scope ``lm.delta_rule`` (the chunked gated delta rule of the
linear-attention layers: the triangular system, the scan over chunks
and their backward passes, recomputation under ``jax.checkpoint``
included), inside the round module's executions. None where the
program carries no such scope. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.scope_s_per_round(ctx, "lm.delta_rule")
