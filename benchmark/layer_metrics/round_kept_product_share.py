"""Model: the share of the layers' forward matrix-product FLOPs whose
float32 results the rematerialized layers keep, so that the backward
pass does not run them again: the launcher's own counter on the
round's row, ``lm_kept_product_share`` (0 to 1, from shapes when the
round is traced; ``models/hybrid_lm.py``: ``kept_counters``), the
window's median. None where the rows carry no such counter (a program
whose checkpoints keep nothing has none). Source: program counter."""
import statistics


def read(ctx):
    shares = [r["lm_kept_product_share"] for r in ctx["rows"]
              if "lm_kept_product_share" in r]
    return statistics.median(shares) if shares else None
