"""Round program: backend compiles and persistent-cache loads that JAX
reports (``jax.compile`` spans, and ``jax.cache_load`` spans outside
any of them) between the ``round`` spans of the window's first and
last round: every program of the process, where ``compiles_in_window``
sees the registered ones. None where the program records no such span.
Source: program counter."""
from benchmark.harness import stage_reduce


def read(ctx):
    if not stage_reduce.spans_named(ctx, stage_reduce.COMPILE_SPANS):
        return None
    w = ctx["window"]
    first = stage_reduce.round_span(ctx, w["first"])
    last = stage_reduce.round_span(ctx, w["last"])
    if first is None or last is None:
        return None
    inside = [s for s in stage_reduce.spans_named(
        ctx, ("jax.compile", "jax.cache_load"))
        if first[0] <= s[1] and s[1] + s[2] <= last[1]]
    compiles = [s for s in inside if s[0] == "jax.compile"]
    loads = [s for s in inside if s[0] == "jax.cache_load" and not any(
        c[1] <= s[1] and s[1] + s[2] <= c[1] + c[2] + 1e-6
        for c in compiles)]
    return float(len(compiles) + len(loads))
