"""Entry layer: wall between two callbacks that the launcher's own
timers do not cover (dispatch, scalar fetch, row and health writes,
telemetry), mean per round of the window. Source: host clock."""
from benchmark.harness import window


def read(ctx):
    w = ctx["window"]
    return window.host_gap_s_per_round(ctx["stamps"], ctx["rows"],
                                       w["first"], w["last"])
