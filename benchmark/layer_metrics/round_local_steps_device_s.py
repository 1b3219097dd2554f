"""Round program: device self seconds a traced round under the scope
``fed.local_steps`` (the K-step scan over the cohort, with its
``fed.augment``, ``fed.forward_backward`` and ``fed.opt_step``), inside
the round module's executions. An operation the compiler hoists out of
the loop keeps the scope of the line that made it. Source: device
trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.stage_s_per_round(ctx, stage_reduce.LOCAL_STAGES)
