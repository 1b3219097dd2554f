"""Model: device self seconds a traced round of the expert layers: the
operations under the scopes ``lm.router`` (the router's product,
softmax and top-k, the counting sort and the buffer's fill) and
``lm.experts`` (the grouped products' casts and masks, the activation
and the combine), and the grouped products themselves, which XLA:TPU
runs as custom calls named ``ragged-dot-*`` that carry no scope of the
program (their framework name is that name alone: read by it, as
``round_recompute_device_s`` reads its tag); forward, recomputation and
backward, inside the round module's executions. None where the program
carries no ``lm.experts`` scope. Source: device trace."""
from benchmark.harness import scope_sum, tag_reduce

SCOPES = ("lm.experts", "lm.router")
KERNELS = "ragged-dot"


def read(ctx):
    seconds = scope_sum.seconds_per_round(ctx, SCOPES)
    if seconds is None:
        return None
    # one more pass over the profile: once a run, for both readers
    if "experts_kernels_s" not in ctx:
        ctx["experts_kernels_s"] = tag_reduce.tagged_s_per_round(ctx, KERNELS)
    return seconds + (ctx["experts_kernels_s"] or 0.0)
