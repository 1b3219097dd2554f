"""Round program: tokens trained a round (the launcher's own counter
on the round's row, ``tokens_trained``) over the window's median
``round_s`` and the cell's chips. None where the rows carry no such
counter. Source: program span."""
import statistics


def read(ctx):
    rows = [r for r in ctx["rows"] if "tokens_trained" in r]
    if not rows:
        return None
    return statistics.median(r["tokens_trained"] for r in rows) \
        / statistics.median(r["round_s"] for r in rows) \
        / ctx["cell"]["chips"]
