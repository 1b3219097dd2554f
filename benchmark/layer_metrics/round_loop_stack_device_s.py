"""Round program: device self seconds a traced round of a looped
model's stack of layers, all its passes: the operations under the
scopes ``lm.loop`` (the scan over passes: projections, norms, rotary
embedding), ``lm.attention`` and ``lm.mlp``, forward, recomputation
and backward, inside the round module's executions. None where the
program carries no ``lm.loop`` scope. Source: device trace."""
from benchmark.harness import scope_sum


def read(ctx):
    return scope_sum.seconds_per_round(ctx, scope_sum.LOOP_STACK)
