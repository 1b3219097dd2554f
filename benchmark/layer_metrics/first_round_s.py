"""Round program, compile side: round 0's ``round_s`` (trace, compile
or cache load, first execution). Source: program span."""


def read(ctx):
    rows = [r for r in ctx["all_rows"] if r["round"] == 0]
    return rows[0]["round_s"] if rows else None
