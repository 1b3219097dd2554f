"""Round program: device self seconds a traced round under ``fed.wire``
... ``fed.metrics`` (payload and wire format, guards, aggregation,
server step, client state written back, metrics). Source: device
trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.stage_s_per_round(ctx, stage_reduce.COMMIT_STAGES)
