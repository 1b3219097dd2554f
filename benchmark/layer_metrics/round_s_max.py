"""Round program: the largest ``round_s`` of the window, where the
window holds too few rounds for a 95th percentile. Source: program
span."""


def read(ctx):
    return max(r["round_s"] for r in ctx["rows"]) if ctx["rows"] else None
