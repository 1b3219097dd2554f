"""Entry layer: the launcher's ``round.record`` spans of a round (log
lines, row assembly, metrics row, events, health file), median over
the window's train-only iterations. Source: program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.median_span_s(ctx, "round.record")
