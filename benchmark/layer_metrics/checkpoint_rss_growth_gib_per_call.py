"""Checkpoint layer: GiB by which the process's resident set grew over
a checkpoint: ``vm_rss_exit`` less ``vm_rss_enter`` of the loop's
``checkpoint`` span (``VmRSS`` of ``/proc/self/status``), mean over the
window's checkpoints: what a save leaves resident, 0 where its buffers
are found again, about the payload where they are new and kept. None
where the span carries no such args. Source: program counter."""
from benchmark.layer_metrics import checkpoint_unspanned_s_per_call


def rss_growth_gib(ctx, name):
    """Mean over the window's loop spans called ``name`` of the GiB
    between their ``vm_rss_enter`` and ``vm_rss_exit`` args."""
    grown = [s[3]["vm_rss_exit"] - s[3]["vm_rss_enter"]
             for s in checkpoint_unspanned_s_per_call.loop_calls(ctx, name)
             if "vm_rss_exit" in s[3] and "vm_rss_enter" in s[3]]
    return sum(grown) / len(grown) / 2 ** 30 if grown else None


def read(ctx):
    return rss_growth_gib(ctx, "checkpoint")
