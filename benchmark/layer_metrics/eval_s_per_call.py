"""Eval layer: mean ``eval_s`` of the window's rows. Source: program
span."""


def read(ctx):
    v = [r["eval_s"] for r in ctx["rows"] if "eval_s" in r]
    return sum(v) / len(v) if v else None
