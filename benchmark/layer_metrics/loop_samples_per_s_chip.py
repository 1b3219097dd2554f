"""Entry layer: the whole loop's rate over the window, rounds x k x K x B
over its seconds and chips, with evaluation, checkpoint, telemetry and
host gaps inside. It was the end-to-end ``samples_per_s_chip`` until
PR 31: evaluation and save read in two modes by the heap's history, so
its runs spread wider than any bound the contract allows (PERF.md
section 2). A traced run's window holds the traced cycles too. Source:
host clock."""
from benchmark.harness import window


def read(ctx):
    if not ctx["rows"]:
        return None
    return window.samples_per_s_chip(
        len(ctx["rows"]), ctx["samples_per_round"],
        ctx["window"]["seconds"], ctx["cell"]["chips"])
