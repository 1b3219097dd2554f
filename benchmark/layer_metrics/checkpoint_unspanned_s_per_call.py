"""Checkpoint layer: seconds of the loop's ``checkpoint`` span that lie
under none of the save's leaf spans (the snapshot, the layout, the
digest, each file's data, fsync and rename, the link, the keeps'
collection), mean over the window's checkpoints: what the span tree
does not explain (the meta's JSON, the retry's back-off, memory given
back). None where the program records no ``checkpoint.layout`` span
(a save that is one node). Source: program span."""
from benchmark.harness import stage_reduce

LEAVES = ("checkpoint.snapshot", "checkpoint.layout", "checkpoint.digest",
          "checkpoint.file_write.data", "checkpoint.file_write.fsync",
          "checkpoint.file_write.rename", "checkpoint.link",
          "checkpoint.gc")


def loop_calls(ctx, name):
    """The loop's spans called ``name`` (``eval`` or ``checkpoint``)
    of the window's rounds; the drain's final save is not one."""
    w = ctx["window"]
    return [s for s in stage_reduce.spans_named(ctx, (name,))
            if w["first"] <= s[3].get("round", -1) <= w["last"]
            and not s[3].get("drain")]


def inside(spans, parent):
    """Those of ``spans`` that lie inside the span ``parent``."""
    _, start, dur, _ = parent
    return [s for s in spans if start - 1e-6 <= s[1]
            and s[1] + s[2] <= start + dur + 1e-6]


def read(ctx):
    calls = loop_calls(ctx, "checkpoint")
    if not calls or not stage_reduce.spans_named(
            ctx, ("checkpoint.layout",)):
        return None
    leaves = stage_reduce.spans_named(ctx, LEAVES)
    return sum(call[2] - sum(s[2] for s in inside(leaves, call))
               for call in calls) / len(calls)
