"""Round program: device self seconds a traced round under
``fed.local_steps`` whose innermost scope is ``fed.augment`` (the
flip-and-crop of each step's batch; 0 where augmentation is off).
Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.local_s_per_round(ctx, "fed.augment")
