"""Eval layer: GiB by which the process's resident set grew over an
evaluation: ``vm_rss_exit`` less ``vm_rss_enter`` of the loop's
``eval`` span, mean over the window's evaluations: which of the
allocator's two modes the evaluations read. 0 where the padded copy of
the test set is served from memory the process holds already, the
copy's size where it is mapped and faulted in anew (the slow mode).
None where the span carries no such args. Source: program counter."""
from benchmark.layer_metrics import checkpoint_rss_growth_gib_per_call


def read(ctx):
    return checkpoint_rss_growth_gib_per_call.rss_growth_gib(ctx, "eval")
