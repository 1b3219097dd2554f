"""Round program: model FLOPs of one round from shapes (k clients x K
steps x B images x the architecture's training FLOPs per image, from
``benchmark/flops/<arch>.py``) over the window's median ``round_s``,
over chips x the bf16 peak of ``benchmark/peaks.json``. A utilization of
the round's wall, not of a kernel. Source: program span."""
import json
import os

from benchmark.harness import runner


def read(ctx):
    import statistics

    cfgf = ctx["cell"]["config_file"]
    flops = runner.load_by_name("flops", cfgf["arch"])
    with open(os.path.join(runner.BENCH, "peaks.json")) as f:
        peaks = json.load(f)["devices"]
    kind = ctx["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    per_round = ctx["samples_per_round"] * flops.train_flops_per_image()
    round_s = statistics.median(r["round_s"] for r in ctx["rows"])
    peak = peaks[kind]["bf16_flops_per_s"] * ctx["cell"]["chips"]
    return 100.0 * per_round / round_s / peak
