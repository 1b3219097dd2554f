"""Model: device self seconds a traced round under the scope
``lm.shared``: the shared expert's SwiGLU, which every chip of an
expert-parallel group computes whole beside its routed share; forward,
recomputation and backward, inside the round module's executions. None
where the program carries no such scope. Source: device trace."""
from benchmark.harness import scope_reduce


def read(ctx):
    return scope_reduce.scope_s_per_round(ctx, "lm.shared")
