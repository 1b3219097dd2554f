"""Eval layer: seconds of an evaluation spent making its inputs: the
``eval.batches`` span (the whole test set padded and reshaped into
batches on the host: a fresh copy of it every call) plus ``eval.h2d``
(its upload), inside the loop's ``eval`` span, mean over the window's
evaluations. None where the program records no such spans. Source:
program span."""
from benchmark.harness import stage_reduce
from benchmark.layer_metrics import checkpoint_unspanned_s_per_call


def seconds_inside_eval(ctx, names):
    """Mean over the window's evaluations of the seconds under the
    spans called ``names`` inside the loop's ``eval`` span; None where
    the run holds none of them."""
    found = stage_reduce.spans_named(ctx, names)
    calls = checkpoint_unspanned_s_per_call.loop_calls(ctx, "eval")
    if not found or not calls:
        return None
    return sum(s[2] for call in calls
               for s in checkpoint_unspanned_s_per_call.inside(
                   found, call)) / len(calls)


def read(ctx):
    return seconds_inside_eval(ctx, ("eval.batches", "eval.h2d"))
