"""Data layer: seconds under the launcher's ``data.partition`` and
``data.layout`` spans (the partition scheme, then the padded
``[clients, N, ...]`` stack), the part of ``data.build`` after the
loader's. Source: program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    spans = stage_reduce.spans_named(ctx, ("data.partition", "data.layout"))
    return stage_reduce.union_s(spans) if spans else None
