"""Data layer: seconds under the launcher's ``trainer.build`` (with the
store's ``data.h2d`` inside it) and ``state.init`` spans: trainer
construction, the store placed on the device, server and client state.
Source: program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    spans = stage_reduce.spans_named(
        ctx, ("trainer.build", "state.init", "data.h2d"))
    return stage_reduce.union_s(spans) if spans else None
