"""Checkpoint layer: mean ``checkpoint_s`` of the window's rows.
Source: program span."""


def read(ctx):
    v = [r["checkpoint_s"] for r in ctx["rows"] if "checkpoint_s" in r]
    return sum(v) / len(v) if v else None
