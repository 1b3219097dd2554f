"""Eval layer: seconds of an evaluation from the call of its compiled
program to its result on the host: the ``eval.dispatch`` span (the
jitted call's return: milliseconds, and a retrace or a compile where
it is not) plus ``eval.fetch`` (the ``device_get`` of the result: the
wait for the device is in here), inside the loop's ``eval`` span, mean
over the window's evaluations. With ``eval_input_s_per_call`` it makes
up ``eval_s_per_call``. None where the program records no such spans.
Source: program span."""
from benchmark.layer_metrics import eval_input_s_per_call


def read(ctx):
    return eval_input_s_per_call.seconds_inside_eval(
        ctx, ("eval.dispatch", "eval.fetch"))
