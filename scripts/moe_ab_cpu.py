"""Sparse-MoE dispatch A/B off-chip.

Runs the SAME cases as ``scripts/moe_ab_bench.py`` (dense exact
dispatch vs Switch sparse capacity dispatch at cf 1.0/1.25/2.0, full
train steps with aux loss) on the XLA CPU backend, recording what IS
hardware-independent:

* executed-FLOPs ratio per case (XLA cost analysis — dense dispatch
  books E× the expert-MLP FLOPs; sparse books ~cf×/E of that),
* per-layer token drop fractions at each capacity factor,
* same-seed loss trajectories (sparse must track dense closely),
* CPU step-time ratios (directional only — no MXU; recorded with that
  caveat).

Additionally times the expert-parallel layer (``parallel/expert.py:
ep_moe_apply``) dense-vs-sparse on the 8-device virtual CPU mesh, the
deployment shape for E=16 at scale.

The on-chip A/B (queued in scripts/tpu_capture_r5.sh) stays the
decision authority for absolute times; this artifact is the evidence
basis for the recommended-config note in docs/performance.md.

Writes MOE_AB_CPU.json; prints one JSON line.
"""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# 1-core host: shrink the workload before moe_ab_bench reads its env
# knobs at import time. Dense E=16 at these sizes is ~tens of GFLOPs
# per step — minutes total, not hours.
os.environ.setdefault("MOE_AB_BATCH", "2")
os.environ.setdefault("MOE_AB_SEQ", "128")
os.environ.setdefault("MOE_AB_ITERS", "3")
os.environ.setdefault("MOE_AB_LOSS_STEPS", "12")
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()

OUT = os.path.join(REPO, "MOE_AB_CPU.json")


def log(msg):
    print(f"[moe_ab_cpu] {msg}", file=sys.stderr, flush=True)


def ep_mesh_ab():
    """Layer-level dense-vs-sparse timing with experts sharded over an
    8-device 'ep' axis — the virtual-mesh half of VERDICT r4 #6."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh
    from fedtorch_tpu.parallel.expert import ep_moe_apply
    from fedtorch_tpu.models.transformer import MoEMLP

    E, D, B, T = 16, 256, 2, 128
    devs = jax.devices()
    if len(devs) < 8:
        raise RuntimeError(
            f"expected the 8-device virtual mesh, found {len(devs)} "
            "devices (a pre-existing XLA_FLAGS device count?) — "
            "refusing to record mislabeled ep timings")
    mesh = Mesh(np.array(devs[:8]), ("ep",))
    x = jax.random.normal(jax.random.key(2), (B, T, D), jnp.float32)
    params = MoEMLP(num_experts=E).init(  # d inferred from x
        jax.random.key(0), x)["params"]

    rows = {}
    for name, cf in (("dense", 0.0), ("cf1.25", 1.25)):
        out = ep_moe_apply(params, x, mesh, capacity_factor=cf)
        jax.block_until_ready(out)  # compile
        t0 = time.time()
        for _ in range(5):
            out = ep_moe_apply(params, x, mesh, capacity_factor=cf)
        jax.block_until_ready(out)
        rows[name] = round((time.time() - t0) / 5 * 1e3, 2)
        log(f"ep-mesh {name}: {rows[name]} ms/layer-fwd")
    rows["sparse_cf1.25_speedup"] = round(
        rows["dense"] / rows["cf1.25"], 2)
    return rows


def main() -> int:
    from fedtorch_tpu.utils import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "cpu":
        log("expected the cpu backend — refusing to mislabel")
        return 1

    import moe_ab_bench as ab

    results = {"platform": "cpu (XLA, 1 core; 8-device virtual mesh "
                           "for the ep section)",
               "caveat": ("off-chip: step-time ratios are directional "
                          "(no MXU); flops_per_step ratios, drop "
                          "fractions and loss tracking are hardware-"
                          "independent. On-chip decision authority: "
                          "MOE_AB.json via scripts/tpu_capture_r5.sh"),
               "config": {"batch": ab.B, "seq": ab.T, "experts": ab.E,
                          "d_model": ab.D_MODEL, "layers": ab.LAYERS,
                          "loss_steps": ab.LOSS_STEPS},
               "cases": {}}
    for name, cf in (("dense", 0.0), ("cf1.0", 1.0),
                     ("cf1.25", 1.25), ("cf2.0", 2.0)):
        log(f"running {name} ...")
        results["cases"][name] = ab.run_case(name, cf)
        with open(OUT, "w") as f:
            json.dump(results, f, indent=1)

    dense = results["cases"]["dense"]
    sp = results["cases"]["cf1.25"]
    summary = {}
    if dense.get("flops_per_step") and sp.get("flops_per_step"):
        summary["flops_ratio_dense_over_cf1.25"] = round(
            dense["flops_per_step"] / sp["flops_per_step"], 2)
    summary["steptime_ratio_dense_over_cf1.25"] = round(
        dense["step_ms"] / sp["step_ms"], 2)
    summary["ce_delta_cf1.25_minus_dense"] = round(
        sp["final_ce"] - dense["final_ce"], 4)
    results["summary"] = summary

    try:
        results["ep_mesh_8dev"] = ep_mesh_ab()
    except Exception as e:
        results["ep_mesh_8dev"] = {"error": str(e)[:300]}
        log(f"ep-mesh section failed: {str(e)[:160]}")

    with open(OUT, "w") as f:
        json.dump(results, f, indent=1)
    log(f"wrote {OUT}")
    print(json.dumps({"metric": "moe_dispatch_ab_cpu", **summary}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
