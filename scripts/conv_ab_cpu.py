"""Conv-lowering A/B on the XLA **CPU** backend.

The CPU-side companion of the on-chip conv A/B (``MFU_SWEEP.json`` /
``BENCH_MATMULSIDE_AB.json``): the SAME compiled federated round program (``FederatedTrainer.run_rounds``
— the program ``bench.py`` times) is built twice per batch size, once
with ``conv_impl='conv'`` (grouped conv from per-client weights) and
once with ``conv_impl='matmul'`` (im2col batched matmul,
``models/common.py:MatmulConv``), and timed on XLA-compiled CPU. That
upgrades the round-4 claim from "2.8-5.1x on numpy CPU" to "X× between
XLA-compiled identical programs", with per-row algorithmic FLOPs from
XLA cost analysis of the conv lowering (``scripts/mfu_sweep.py``
accounting — matmul rows do NOT book im2col patch extraction as useful
work).

CAVEAT (recorded in the artifact): the CPU backend has no MXU; the
absolute times say nothing about the v5e, and the conv-vs-matmul
ratio can differ on the chip where the MXU executes large matmuls at
full rate (the reason the matmul lowering should win HARDER there —
the roofline argument in docs/performance.md "MFU roofline"). The
on-chip sweep (`scripts/tpu_capture.sh conv-ab`) remains the decision
authority — and it reversed this table's verdict (5.06x for native
conv, BENCH_MATMULSIDE_AB.json).

Writes CONV_AB_CPU.json; prints one JSON line. Grid sizes via
MFU_CLIENTS/MFU_STEPS/MFU_ROUNDS (kept small: 1-core host).
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# must hold before the first jax backend touch
os.environ.setdefault("MFU_CLIENTS", "8")
os.environ.setdefault("MFU_STEPS", "5")
os.environ.setdefault("MFU_ROUNDS", "2")
os.environ["JAX_PLATFORMS"] = "cpu"

OUT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "CONV_AB_CPU.json")


def log(msg):
    print(f"[conv_ab_cpu] {msg}", file=sys.stderr, flush=True)


def main() -> int:
    from fedtorch_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "cpu":
        log(f"expected cpu backend, got {jax.devices()[0]} — refusing "
            "(this script's numbers are only labeled correctly on CPU)")
        return 1

    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from mfu_sweep import run_config

    rows = []
    for batch in (50, 128):
        for conv_impl in ("conv", "matmul"):
            name = f"b{batch}_{conv_impl}"
            log(f"running {name} ...")
            row = run_config(name, batch=batch, dtype="float32",
                             online_rate=0.25, conv_impl=conv_impl)
            # mfu_pct/achieved_tflops divide CPU wall-clock by the TPU
            # peak — a fabricated MFU; only the chip may report one
            for key in ("mfu_pct", "achieved_tflops", "peak_tflops"):
                row.pop(key, None)
            rows.append(row)

    # pair up the A/Bs; the ratio comes from the UNROUNDED timed
    # segments (same step count per batch config), not the 2-decimal
    # steps/s display values, which quantize to +-20-40% at these
    # magnitudes
    by = {(r["batch"], r["conv_impl"]): r for r in rows}
    speedups = {}
    for batch in (50, 128):
        conv_t = by[(batch, "conv")]["timed_s"]
        mm_t = by[(batch, "matmul")]["timed_s"]
        speedups[f"matmul_vs_conv_b{batch}"] = round(conv_t / mm_t, 2)

    record = {
        "metric": "conv_lowering_ab_xla_cpu",
        "backend": "cpu (XLA, 1 core)",
        "caveat": ("XLA-compiled identical round programs on the CPU "
                   "backend; no MXU — ratios are evidence, not the "
                   "on-chip decision (see the tpu_capture.sh conv-ab "
                   "step). FLOPs numerator is the conv lowering's "
                   "cost analysis for every row. Speedups are ratios "
                   "of the unrounded timed segments (identical step "
                   "counts per batch)."),
        "rows": rows,
        "speedups": speedups,
        "captured_at": time.strftime("%Y-%m-%dT%H:%M:%SZ",
                                     time.gmtime()),
        "grid": {k: os.environ[k] for k in
                 ("MFU_CLIENTS", "MFU_STEPS", "MFU_ROUNDS")},
    }
    with open(OUT, "w") as f:
        json.dump(record, f, indent=1)
    log(f"wrote {OUT}")
    print(json.dumps({"metric": record["metric"],
                      "speedups": speedups}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
