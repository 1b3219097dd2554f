"""Round program: device self seconds a traced round under
``fed.local_steps`` whose innermost scope is ``fed.fold`` (the
sequential execution's running weighted sum: its zeroing and, once a
client, the fold of the client's movement into it). None where the
program carries no stage name at all. Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.local_s_per_round(ctx, "fed.fold")
