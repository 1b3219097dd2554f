"""Round program: device self seconds a traced round under
``fed.select`` + ``fed.gather`` + ``fed.pre_round`` (participation and
row plan, rows out of the store, the cohort's client state, the
pre-round hook). Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.stage_s_per_round(ctx, stage_reduce.GATHER_STAGES)
