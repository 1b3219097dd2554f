"""Checkpoint layer: seconds of a checkpoint under the program's
``checkpoint.file_write`` spans (one per file written: open, write,
flush, fsync, rename), summed over the checkpoint's files, mean over
the window's checkpoints. None where the program records no such span.
Source: program span."""
from benchmark.harness import stage_reduce


def spans_by_checkpoint(ctx, name):
    """The spans called ``name`` of each checkpoint of the window, one
    list a checkpoint: those inside the loop's ``checkpoint`` span of a
    window round (the rounds whose row holds ``checkpoint_s``; not the
    drain's final save). None where the run holds no span of that name
    at all."""
    found = stage_reduce.spans_named(ctx, (name,))
    if not found:
        return None
    w = ctx["window"]
    out = []
    for _, start, dur, args in stage_reduce.spans_named(ctx, ("checkpoint",)):
        if w["first"] <= args.get("round", -1) <= w["last"] \
                and not args.get("drain"):
            out.append([s for s in found if start - 1e-6 <= s[1]
                        and s[1] + s[2] <= start + dur + 1e-6])
    return out or None


def mean_seconds(ctx, name):
    """Mean over the window's checkpoints of the seconds under a
    checkpoint's spans called ``name``."""
    calls = spans_by_checkpoint(ctx, name)
    if calls is None:
        return None
    return sum(s[2] for call in calls for s in call) / len(calls)


def read(ctx):
    return mean_seconds(ctx, "checkpoint.file_write")
