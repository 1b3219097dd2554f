"""Data layer: the launcher's ``data.build`` span (loader, partition,
padded layout, model definition). Source: program span."""


def read(ctx):
    durs = [d for n, _, d, _ in ctx["spans"]["spans"] if n == "data.build"]
    return durs[0] if durs else None
