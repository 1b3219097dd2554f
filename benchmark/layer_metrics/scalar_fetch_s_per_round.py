"""Entry layer: the launcher's ``scalar_fetch`` span (the round's
scalars built on the device and fetched in one ``device_get``), median
over the window's train-only iterations. Source: program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    return stage_reduce.median_span_s(ctx, "scalar_fetch")
