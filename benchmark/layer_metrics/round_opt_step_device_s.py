"""Round program: device self seconds a traced round under
``fed.local_steps`` whose innermost scope is ``fed.opt_step`` (the
optimizer's update, as far as the compiler leaves it apart from the
gradient's fusions). Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.local_s_per_round(ctx, "fed.opt_step")
