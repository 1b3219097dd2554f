"""Telemetry layer: the ``cost_capture`` span (the twin programs
lowered and compiled once after round 0). Source: program span."""


def read(ctx):
    durs = [d for n, _, d, _ in ctx["spans"]["spans"]
            if n == "cost_capture"]
    return durs[0] if durs else None
