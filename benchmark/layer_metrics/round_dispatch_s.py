"""Entry layer: the launcher's ``round.dispatch`` span (until
``run_round`` returns: argument handling and enqueue), median over the
window's iterations that hold a train round and nothing else. Source:
program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.median_span_s(ctx, "round.dispatch")
