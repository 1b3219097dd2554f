"""Eval layer: device self seconds under the scope ``eval.forward``
inside the evaluation program's executions of the traced cycle, per
execution: what of ``eval_s_per_call`` the device works. None where no
operation of the profile carries the scope. Source: device trace."""
from benchmark.harness import stage_reduce


def read(ctx):
    red = stage_reduce.get(ctx)
    if not red or not red["eval_runs"]:
        return None
    return red["eval_s"] / red["eval_runs"]
