"""Round program: token-passes a second and chip: the launcher's own
counters on the round's row, ``tokens_trained`` x ``ut_steps`` (the
passes a token makes through a looped model's layers), over the
window's median ``round_s`` and the cell's chips. None where the rows
carry no such counters. Source: program span."""
import statistics


def read(ctx):
    rows = [r for r in ctx["rows"]
            if "tokens_trained" in r and "ut_steps" in r]
    if not rows:
        return None
    return statistics.median(r["tokens_trained"] * r["ut_steps"]
                             for r in rows) \
        / statistics.median(r["round_s"] for r in rows) \
        / ctx["cell"]["chips"]
