"""Round program: device self seconds a traced round of the operations
under the scope ``lm.attention`` (the full-attention layer's causal
softmax core: the flash kernel's forward, its chunked backward, and
the forward's recomputation under ``jax.checkpoint``), inside the round
module's executions. None where the program carries no such scope.
Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.scope_s_per_round(ctx, "lm.attention")
