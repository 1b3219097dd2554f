"""Data layer: the launcher's ``data.load`` span (files read and
normalised), the loader's part of ``data.build``. Source: program
span."""
from benchmark.harness import stage_reduce


def read(ctx):
    spans = stage_reduce.spans_named(ctx, ("data.load",))
    return spans[0][2] if spans else None
