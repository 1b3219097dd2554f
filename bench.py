"""Benchmark: FedAvg local-SGD throughput on the north-star config.

Workload (BASELINE.json): FedAvg, ResNet-20, CIFAR-10-shaped data, 100
clients, batch 50, 10 local steps/round, 10% participation — measured as
**local-steps/sec/chip** on the TPU, and nowhere else: with no TPU the
script exits non-zero before it compiles anything.

``vs_baseline`` compares against the reference's per-process torch-CPU
local-step rate on the same host, measured live by running the
reference's own ResNet-20 training step from /root/reference; it is
``null`` when the reference is not mounted. The reference has no
published numbers (SURVEY.md §6), so its own hot loop is the baseline.

Prints exactly ONE JSON line on stdout, stamped with the device as JAX
reports it; diagnostics go to stderr. Defining the benchmark proper
(cells, per-layer metrics, regression bounds) is ROADMAP Speed 1.
"""
from __future__ import annotations

import json
import os
import sys
import time

NUM_CLIENTS = 100
BATCH_SIZE = 50
LOCAL_STEPS = 10
ONLINE_RATE = 0.1
SAMPLES_PER_CLIENT = 250
TIMED_ROUNDS = 5
ARCH = "resnet20"
DATASET = "cifar10"

# The A/B env knobs and their north-star defaults.
BENCH_AB_KNOBS = {
    # 'auto' = the SHIPPED default lowering (resolves to native conv on
    # TPU for resnet20/cifar10; models/__init__.py resolve_conv_impl).
    "BENCH_CONV_IMPL": "auto",
    "BENCH_DTYPE": "bfloat16",
    "BENCH_SCAN_UNROLL": "1",
    "BENCH_SINGLE_DISPATCH": "1",
    # BENCH_STREAMING=1 runs the round loop on the streaming data
    # plane (--data_plane stream); composes with BENCH_SINGLE_DISPATCH
    # (the round-program builder's feed x scan cell).
    "BENCH_STREAMING": "0",
}


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def ab_knob(name: str) -> str:
    return os.environ.get(name, BENCH_AB_KNOBS[name])


def measure_torch_baseline() -> "float | None":
    """The reference's own ResNet-20 step loop on this host's CPU, in
    steps/s; ``None`` when /root/reference (or torch) is not there."""
    try:
        import types
        sys.path.insert(0, "/root/reference")
        import torch
        import fedtorch.components.models as ref_models
    except ImportError as e:
        log(f"torch baseline unavailable ({e}); vs_baseline is null")
        return None
    model = ref_models.resnet(
        types.SimpleNamespace(arch=ARCH, data=DATASET))
    opt = torch.optim.SGD(model.parameters(), lr=0.1)
    crit = torch.nn.CrossEntropyLoss()
    x = torch.randn(BATCH_SIZE, 3, 32, 32)
    y = torch.randint(0, 10, (BATCH_SIZE,))

    def steps(n):
        for _ in range(n):
            opt.zero_grad()
            crit(model(x), y).backward()
            opt.step()

    steps(2)
    n = 10
    t0 = time.time()
    steps(n)
    rate = n / (time.time() - t0)
    log(f"torch-cpu baseline measured live: {rate:.2f} steps/s")
    return rate


def main():
    from fedtorch_tpu.utils import enable_compile_cache, require_tpu
    device = require_tpu("bench.py")
    log(f"device: {device}")
    log(f"persistent compile cache: {enable_compile_cache()}")

    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model, resolve_conv_impl
    from fedtorch_tpu.parallel import FederatedTrainer

    # bf16 conv/matmul compute on the MXU (params/norms stay f32);
    # override with BENCH_DTYPE=float32 for a full-precision run.
    dtype = ab_knob("BENCH_DTYPE")
    streaming = ab_knob("BENCH_STREAMING") == "1"
    conv_impl = resolve_conv_impl(ab_knob("BENCH_CONV_IMPL"), ARCH,
                                  DATASET)
    log(f"compute dtype: {dtype}; conv lowering: {conv_impl}")
    cfg = ExperimentConfig(
        data=DataConfig(dataset=DATASET, batch_size=BATCH_SIZE,
                        data_plane="stream" if streaming else "device"),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            online_client_rate=ONLINE_RATE, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch=ARCH, conv_impl=conv_impl),
        optim=OptimConfig(lr=0.1, in_momentum=True),
        train=TrainConfig(local_step=LOCAL_STEPS),
        mesh=MeshConfig(compute_dtype=dtype,
                        scan_unroll=int(ab_knob("BENCH_SCAN_UNROLL"))),
    ).finalize()

    # CIFAR-10-shaped synthetic client shards (no network here; shapes
    # and dtypes identical to the real set).
    rng = np.random.RandomState(0)
    feats = rng.randn(NUM_CLIENTS * SAMPLES_PER_CLIENT, 32, 32,
                      3).astype(np.float32)
    labels = rng.randint(0, 10, NUM_CLIENTS * SAMPLES_PER_CLIENT)
    parts = [np.arange(i * SAMPLES_PER_CLIENT, (i + 1) * SAMPLES_PER_CLIENT)
             for i in range(NUM_CLIENTS)]
    data = stack_partitions(feats, labels, parts)

    model = define_model(cfg, batch_size=BATCH_SIZE)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))

    # timed segment: all rounds in ONE device call (lax.scan over the
    # round program — no per-round host dispatch); BENCH_SINGLE_DISPATCH=0
    # reverts to the per-round loop for A/B. Each mode warms up (and
    # compiles) only ITS OWN program.
    batched = ab_knob("BENCH_SINGLE_DISPATCH") == "1"
    if batched:
        t0 = time.time()
        server, clients, _ = trainer.run_rounds(server, clients,
                                                TIMED_ROUNDS)
        jax.block_until_ready(server.params)
        setup_s = time.time() - t0
        t0 = time.time()
        server, clients, _ = trainer.run_rounds(server, clients,
                                                TIMED_ROUNDS)
        jax.block_until_ready(server.params)
        dt = time.time() - t0
    else:
        t0 = time.time()
        server, clients, _ = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
        setup_s = time.time() - t0
        t0 = time.time()
        for _ in range(TIMED_ROUNDS):
            server, clients, _ = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
        dt = time.time() - t0
    log(f"compile + first call: {setup_s:.1f}s")

    n_chips = int(trainer.mesh.devices.size)
    steps = TIMED_ROUNDS * trainer.k_online * trainer.local_steps
    steps_per_sec = steps / dt / n_chips
    log(f"{steps} local steps in {dt:.2f}s over {TIMED_ROUNDS} rounds "
        f"on {n_chips} chip(s)")

    # MFU: per-local-step FLOPs from the shared XLA cost-analysis probe
    # (telemetry.costs — the same numerator mfu_sweep.py reports) when
    # the timed program is the conv lowering; the analytic resnet20
    # constant when the backend reports no costs or the timed row is
    # the matmul lowering (whose im2col patch extraction must not be
    # booked as useful work). No MFU at all for a device or dtype the
    # peaks table does not list.
    from fedtorch_tpu.telemetry.costs import (
        FLOPS_ANALYTIC, FLOPS_XLA, analytic_train_flops_per_image,
        resolve_peak_tflops, train_step_flops,
    )
    peak_tflops, peak_source = resolve_peak_tflops(device["kind"], dtype)
    mfu_pct = flops_source = None
    if peak_tflops is not None:
        step_flops = train_step_flops(model, BATCH_SIZE) \
            if conv_impl == "conv" else None
        flops_source = FLOPS_XLA
        if step_flops is None:
            step_flops = BATCH_SIZE * analytic_train_flops_per_image(ARCH)
            flops_source = FLOPS_ANALYTIC
        achieved = steps_per_sec * step_flops
        mfu_pct = round(100 * achieved / (peak_tflops * 1e12), 2)
        log(f"MFU estimate: {mfu_pct}% of {peak_tflops} TFLOPs/chip "
            f"[{peak_source}] ({achieved/1e12:.2f} TFLOPs/s/chip "
            f"achieved, flops={flops_source})")

    baseline = measure_torch_baseline()
    note = ("CIFAR-shaped synthetic shards; dispatch="
            + ("batched-scan" if batched else "per-round"))
    if streaming:
        note += "; data_plane=stream"
    record = {
        "metric": "fedavg_resnet20_cifar10_100clients_local_steps_per_sec_per_chip",
        "value": round(steps_per_sec, 2),
        "unit": "local-steps/sec/chip",
        "vs_baseline": None if baseline is None
        else round(steps_per_sec / baseline, 2),
        "device": device,
        "setup_s": round(setup_s, 1),
        "mfu_pct": mfu_pct,
        "flops_source": flops_source,
        "notes": note,
    }
    print(json.dumps(record), flush=True)


if __name__ == "__main__":
    main()
