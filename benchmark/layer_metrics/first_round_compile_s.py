"""Round program, compile side: seconds of round 0's ``round`` span
under JAX's own ``jax.trace`` / ``jax.lower`` / ``jax.compile`` /
``jax.cache_load`` spans (nested ones once): what of ``first_round_s``
is tracing, lowering and compiling or loading. Source: program span."""
from benchmark.harness import stage_reduce


def read(ctx):
    spans = stage_reduce.spans_named(ctx, stage_reduce.COMPILE_SPANS)
    r0 = stage_reduce.round_span(ctx, 0)
    if not spans or r0 is None:
        return None
    return stage_reduce.union_s(spans, inside=r0)
