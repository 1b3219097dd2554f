"""MFU sweep on the north-star workload.

Round 2 measured 3.67% MFU on the north-star config (FedAvg, ResNet-20,
100 clients, batch 50, k=10 online, bf16) and hypothesized an
MXU-underfill regime (32x32 convs, small per-client batches, grouped
convs from per-client weights) without measuring any lever. This script
measures the levers: it times the REAL federated trainer
(`FederatedTrainer.run_rounds`, the same program `bench.py` times) under
a grid of configurations and reports local-steps/sec/chip + analytic
MFU for each:

  base          B=50  bf16 unroll=1 k=10   (the north-star itself)
  batch128      B=128 — 2.56x more rows per conv call
  batch256      B=256 — 5.12x
  f32           B=50 float32 — is bf16 actually buying anything?
  unroll4       B=50 unroll=4 — XLA software-pipelines local steps
  batch128u4    B=128 unroll=4 — the two levers combined
  online20      B=50 k=20 — more clients in flight per round
  matmulconv    B=50 conv_impl=matmul — im2col batched-matmul lowering
  matmulconv128 B=128 conv_impl=matmul — both levers
  resnet50      B=50 — bottleneck blocks reach 256 output channels,
                escaping the N-lane roofline bound (underfill is the
                benchmark model, not the engine)
  fused         B=50 client_fusion=fused — the k online clients packed
                into ONE feature_group_count=k grouped conv per layer
                (k x the MXU lanes per pass; CPU-proven bitwise
                equivalent, tests/test_client_fusion.py) — round 6's
                utilization lever
  fused_online20  B=50 k=20 fused — 20 x the lanes
  fused128      B=128 fused — rows AND lanes together

MFU accounting: per-local-step FLOPs come from XLA's cost analysis of
the compiled fwd+bwd of the ``conv_impl='conv'`` lowering — the
algorithmic work — for EVERY row, so matmul-conv rows don't count
im2col patch extraction as useful FLOPs and mfu_pct is comparable
across the conv A/B (each row's ``flops_source`` says so — exact for
any arch, includes norms/elementwise, memoized per
(arch, batch, dtype)); when the backend reports none,
resnet20 rows fall back to bench.py's analytic constant (fwd =
40.8e6 MACs/image, train step = 3x fwd, 2 FLOPs/MAC) and other archs
report timing without an MFU. The peak comes from the device-kind
table in ``telemetry.costs.PEAK_TFLOPS``; a device or dtype it does not
list (float32 included) reports timing without an MFU.

``MFU_PROFILE=1`` additionally captures a jax.profiler trace of the
base config's timed segment to artifacts/trace_northstar/ for the
roofline note.

Writes MFU_SWEEP.json; prints one JSON line. TPU only: CPU numbers
would answer nothing about the MXU, so main() exits non-zero without a
chip, and non-zero when any configuration failed. To smoke-test the
plumbing off-chip, import ``run_config`` directly, e.g.

    JAX_PLATFORMS=cpu MFU_CLIENTS=8 MFU_STEPS=2 MFU_ROUNDS=1 python -c "
    import sys; sys.path[:0] = ['scripts', '.']
    from mfu_sweep import run_config
    print(run_config('smoke', batch=8, online_rate=0.25))"
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from fedtorch_tpu.telemetry.costs import (
    FLOPS_ANALYTIC, FLOPS_XLA, analytic_train_flops_per_image,
    resolve_peak_tflops, train_step_flops,
)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


# env-overridable for CPU smoke tests of the plumbing (the measured
# grid always runs the real sizes)
NUM_CLIENTS = int(os.environ.get("MFU_CLIENTS", "100"))
LOCAL_STEPS = int(os.environ.get("MFU_STEPS", "10"))
TIMED_ROUNDS = int(os.environ.get("MFU_ROUNDS", "5"))
TRAIN_FLOPS_PER_IMAGE = analytic_train_flops_per_image("resnet20")


_FLOPS_CACHE = {}


def measured_flops_per_step(model, batch, cache_key=None):
    """Per-local-step training FLOPs from XLA's own cost analysis of
    the compiled fwd+bwd (the compiled truth, vs the hand-derived
    resnet20 constant) — delegated to the ONE shared probe,
    ``telemetry.costs.train_step_flops``, so every bench reports the
    same ``flops_source`` accounting. None when the backend doesn't
    report flops (any failure is absorbed — a lost FLOPs count must
    never lose the config's timing). Memoized on ``cache_key`` so grid
    configs that share (arch, batch, dtype) pay one compile (callers
    always pass the conv-lowering model, whatever the timed row's
    conv_impl)."""
    if cache_key is not None and cache_key in _FLOPS_CACHE:
        return _FLOPS_CACHE[cache_key]
    out = train_step_flops(model, batch)
    if out is None:
        log("cost_analysis unavailable; using the analytic constant "
            "where applicable")
    if cache_key is not None:
        _FLOPS_CACHE[cache_key] = out
    return out


def run_config(name, *, batch, dtype="bfloat16", unroll=1,
               online_rate=0.1, conv_impl="conv", arch="resnet20",
               client_fusion="auto", num_devices=None,
               profile_dir=None):
    import jax
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    from fedtorch_tpu.utils.tracing import capture_round_trace

    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=batch),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            online_client_rate=online_rate, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch=arch, conv_impl=conv_impl),
        optim=OptimConfig(lr=0.1, in_momentum=True),
        train=TrainConfig(local_step=LOCAL_STEPS),
        # num_devices: the sweep measures one chip; CPU smoke tests of
        # the fused plumbing pin 1 (their virtual test mesh exposes 8
        # devices, and the fused lowering never shards the client axis)
        mesh=MeshConfig(compute_dtype=dtype, scan_unroll=unroll,
                        client_fusion=client_fusion,
                        num_devices=num_devices),
    ).finalize()

    samples = max(250, batch)  # each client must cover one full batch
    rng = np.random.RandomState(0)
    feats = rng.randn(NUM_CLIENTS * samples, 32, 32, 3).astype(
        np.float32)
    labels = rng.randint(0, 10, NUM_CLIENTS * samples)
    parts = [np.arange(i * samples, (i + 1) * samples)
             for i in range(NUM_CLIENTS)]
    data = stack_partitions(feats, labels, parts)

    # timed drains sync through scripts/bench_timing.py's one rule
    from bench_timing import sync as bench_sync

    model = define_model(cfg, batch_size=batch)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))

    t0 = time.time()
    server, clients, _ = trainer.run_rounds(server, clients,
                                            TIMED_ROUNDS)
    bench_sync(server.params)
    compile_s = time.time() - t0

    t0 = time.time()
    server, clients, _ = trainer.run_rounds(server, clients,
                                            TIMED_ROUNDS)
    bench_sync(server.params)
    dt = time.time() - t0

    if profile_dir:
        # trace capture is a SEPARATE, untimed segment after the
        # recorded one: capture_round_trace runs start_trace AND the
        # trace serialization/disk write around its call, which would
        # otherwise sit inside dt and deflate profiled rows against
        # both their unprofiled siblings and the round-5 history.
        # The hook drains the result inside the trace window with a
        # 1-element fetch (utils/tracing.py) — the on-chip artifact
        # the utilization round attributes the non-MXU time with.
        # Absorbed on failure: a profiler failure must never lose the
        # config's already-measured timing row.
        try:
            server, clients, _ = capture_round_trace(
                profile_dir, trainer.run_rounds, server, clients,
                TIMED_ROUNDS)
            log(f"profiler trace captured to {profile_dir}")
        except Exception as e:
            log(f"profiler capture failed ({str(e)[:160]}); "
                "timing row kept")

    n_chips = int(trainer.mesh.devices.size)
    steps = TIMED_ROUNDS * trainer.k_online * trainer.local_steps
    steps_per_sec = steps / dt / n_chips
    peak_tflops, _peak_src = resolve_peak_tflops(
        jax.devices()[0].device_kind, dtype)
    # FLOPs per local step: XLA cost analysis of the compiled fwd+bwd
    # when available (exact for ANY arch), else the analytic resnet20
    # constant; configs with neither report no MFU rather than a made-up
    # one. The numerator is ALGORITHMIC work — always counted from the
    # conv_impl='conv' lowering, so matmul rows don't book im2col
    # patch-extraction's extra executed FLOPs (~25-55% per 3x3 stage)
    # as useful work and mfu_pct stays apples-to-apples across the
    # conv A/B (the wall-clock columns are the A/B).
    if conv_impl == "conv":
        flops_model = model
    else:
        import dataclasses
        flops_cfg = dataclasses.replace(
            cfg, model=dataclasses.replace(cfg.model, conv_impl="conv"))
        flops_model = define_model(flops_cfg, batch_size=batch)
    step_flops = measured_flops_per_step(
        flops_model, batch, cache_key=(arch, batch, dtype))
    flops_src = FLOPS_XLA
    if step_flops is None:
        if arch == "resnet20":
            step_flops = batch * TRAIN_FLOPS_PER_IMAGE
            flops_src = FLOPS_ANALYTIC
        else:
            flops_src = None
    row = {
        "batch": batch, "dtype": dtype, "scan_unroll": unroll,
        "conv_impl": conv_impl, "arch": arch,
        "client_fusion": trainer.client_fusion,
        "k_online": int(trainer.k_online),
        "local_steps_per_sec_per_chip": round(steps_per_sec, 2),
        "images_per_sec": round(steps_per_sec * batch, 1),
        "peak_tflops": peak_tflops,
        "flops_source": flops_src,
        "compile_plus_first_s": round(compile_s, 1),
        "timed_s": round(dt, 2),
    }
    mfu_pct = None
    if step_flops:
        achieved = steps_per_sec * step_flops
        row["flops_per_step"] = step_flops
        row["achieved_tflops"] = round(achieved / 1e12, 3)
        if peak_tflops is not None:
            mfu_pct = round(100 * achieved / (peak_tflops * 1e12), 2)
            row["mfu_pct"] = mfu_pct
    log(f"{name:12s}: {steps_per_sec:8.2f} steps/s/chip  "
        f"{row['images_per_sec']:9.1f} img/s  "
        f"MFU {mfu_pct if mfu_pct is not None else '?'}%  "
        f"(compile+1st {compile_s:.0f}s, "
        f"flops={row['flops_source']})")
    return row


def main():
    from fedtorch_tpu.utils import enable_compile_cache, require_tpu
    device = require_tpu("mfu_sweep.py")
    enable_compile_cache()
    log(f"device: {device}")

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    profiling = os.environ.get("MFU_PROFILE") == "1"
    profile_dir = os.path.join(repo, "artifacts", "trace_northstar") \
        if profiling else None
    profile_fused = os.path.join(repo, "artifacts",
                                 "trace_northstar_fused") \
        if profiling else None

    # ordered by information value (results persist incrementally)
    grid = [
        ("base", dict(batch=50, profile_dir=profile_dir)),
        # round 6's utilization lever: the k online clients packed
        # into ONE feature_group_count=k grouped conv per layer
        # (k x the MXU output lanes on the 16-64-channel stages;
        # docs/performance.md "Client-fused MXU execution"). Same
        # algorithmic FLOPs as base — mfu_pct is directly comparable;
        # the trace pair (base vs fused) attributes the non-MXU time.
        # num_devices=1: the fusion gate rejects multi-device meshes
        # (the packed client/channel axis must not be sharded), and a
        # host exposing >1 chip would otherwise turn the whole fused
        # A/B into error rows
        ("fused", dict(batch=50, client_fusion="fused", num_devices=1,
                       profile_dir=profile_fused)),
        ("fused_online20", dict(batch=50, online_rate=0.2,
                                client_fusion="fused", num_devices=1)),
        ("fused128", dict(batch=128, client_fusion="fused",
                          num_devices=1)),
        # im2col batched-matmul conv lowering (models/common.py) — the
        # model-level form of vmap_penalty_bench's conv_lowering A/B
        ("matmulconv", dict(batch=50, conv_impl="matmul")),
        ("batch128", dict(batch=128)),
        ("matmulconv128", dict(batch=128, conv_impl="matmul")),
        ("batch256", dict(batch=256)),
        ("f32", dict(batch=50, dtype="float32")),
        ("unroll4", dict(batch=50, unroll=4)),
        ("batch128u4", dict(batch=128, unroll=4)),
        ("online20", dict(batch=50, online_rate=0.2)),
        # bottleneck blocks reach 256 output channels — escapes the
        # N-lane roofline bound (docs/performance.md): high MFU here +
        # low MFU on resnet20 = the underfill is the benchmark model,
        # not the engine
        ("resnet50", dict(batch=50, arch="resnet50")),
    ]
    results = {"device": device,
               "flops_accounting":
                   "per-row flops_source: xla_cost_analysis (compiled "
                   "fwd+bwd, incl. norms/elementwise) or "
                   "analytic_resnet20 (3x fwd, 2 FLOPs/MAC, 40.8e6 "
                   "MACs/img — bench.py's accounting)",
               "configs": {}}
    best = None
    failed = []
    for name, kw in grid:
        try:
            row = run_config(name, **kw)
            results["configs"][name] = row
            mfu = row.get("mfu_pct")
            if mfu is not None and (best is None or mfu > best[1]):
                best = (name, mfu)
        except Exception as e:
            # recorded (an OOM at B=256 is itself a datum) and the
            # sweep goes on, but the exit code says a case failed
            failed.append(name)
            results["configs"][name] = {"error": str(e)[:300]}
            log(f"{name}: FAIL {str(e)[:160]}")
        # persist incrementally — a crash mid-sweep must not lose the
        # configs already measured
        with open(os.path.join(repo, "MFU_SWEEP.json"), "w") as f:
            json.dump(results, f, indent=1)

    print(json.dumps({
        "mfu_sweep_ok": best is not None and not failed,
        "failed": failed,
        "best_config": best[0] if best else None,
        "best_mfu_pct": best[1] if best else None,
        "device": device}))
    return 1 if failed or best is None else 0


if __name__ == "__main__":
    sys.exit(main())
