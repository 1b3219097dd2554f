"""Round program: median of the launcher's own ``round_s`` rows of the
window (its timer around the call into the round program, up to
``block_until_ready``). The end-to-end ``round_s_p50`` is the
benchmark's clock from callback to callback; the difference is the
entry layer's ``host_gap_s_per_round``. Source: program span."""
import statistics


def read(ctx):
    rows = [r["round_s"] for r in ctx["rows"]]
    return statistics.median(rows) if rows else None
