"""Model: the expert layers' share of their roofline. The least time
the chip could take for a round's layer calls of
``flops/<arch>.py:experts_flops`` over the bf16 peak or
``experts_bytes`` over the memory bandwidth, whichever is larger (the
router and the three grouped products, forward and backward), with the
pairs a row-step and layer that the round's own rows count
(``lm_moe_pairs_local``, the window's median) and not the expected
ones, so that uneven routing cannot push the share over 100; over
``round_experts_device_s``. Source: device trace."""
import json
import os
import statistics

from benchmark.harness import runner, scope_reduce
from benchmark.layer_metrics import round_experts_device_s


def read(ctx):
    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    seconds = round_experts_device_s.read(ctx)
    pairs = [r["lm_moe_pairs_local"] for r in ctx["rows"]
             if "lm_moe_pairs_local" in r]
    if not seconds or not pairs or not hasattr(flops, "experts_flops"):
        return None
    s = flops.spec()
    calls = ctx["samples_per_round"] * flops.layer_counts(s)["full"]
    with open(os.path.join(scope_reduce.BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"][ctx["device"]["kind"]]
    here = statistics.median(pairs)
    least = max(
        flops.experts_flops(s["seq_len"], s, here)
        / peak["bf16_flops_per_s"],
        flops.experts_bytes(s["seq_len"], s, here)
        / peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
