"""Pod-scale shard-sweep A/B (ISSUE 20): rounds/sec and clients/sec
vs `mesh.client_shards` on the north-star-shaped workload.

For each S in {1, 2, 4} that divides both the device count and the
dispatch cohort width, builds the stream-plane round program with the
client axis sharded S ways (per-shard vmap slab, on-chip partial sums,
exactly ONE cross-shard all-reduce at the `_round_core` seam) and
records, under the recompilation sentinel:

* steady-state round wall-time (fetch-synced — bench_timing.sync),
  rounds/sec, and clients/sec (= k_dispatch * rounds/sec — the
  pod-scale headline: how fast the pod chews through online clients);
* retraces during the timed window (the sharded program must trace
  exactly once, in warmup — trace-once is a hard bar, not a metric);
* bitwise parity of the final server params against the S=1 arm (the
  hierarchical level-1/level-2 sum is shard-count-invariant by
  construction; this is the run-time proof);
* the pod-scale gauges (`client_shards`, `cohort_allreduce_bytes`,
  per-shard producer walls) off `telemetry_gauges()`.

Writes PODSCALE_AB.json (PODSCALE_AB_PATH overrides, for the test
smoke), seeded with the MULTICHIP_r05.json point when that capture
artifact is present, plus a compare-able run dir (PODSCALE_RUNS_DIR,
default artifacts/podscale_northstar) from the LARGEST shard arm that
the `podscale` capture step gates via `fedtorch-tpu compare --gate
tests/data/ops_runs/podscale_gates.json` against the previous window
(regressed clients/sec fails the capture).

PODSCALE_BENCH_SMOKE=1 shrinks the workload for CPU CI and forces an
8-device host-platform mesh so the shard sweep is real on one CPU.

Run:  python scripts/podscale_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

SMOKE = os.environ.get("PODSCALE_BENCH_SMOKE") == "1"
if SMOKE:
    # the sweep needs a multi-device mesh even on a CPU box — force it
    # BEFORE jax imports (flag is read at backend init)
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")
    os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from fedtorch_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()

from bench_timing import sync  # noqa: E402
from fedtorch_tpu.algorithms import make_algorithm  # noqa: E402
from fedtorch_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data  # noqa: E402
from fedtorch_tpu.models import define_model  # noqa: E402
from fedtorch_tpu.parallel import FederatedTrainer  # noqa: E402
from fedtorch_tpu.utils.tracing import (  # noqa: E402
    RecompilationSentinel,
)

SHARD_SWEEP = (1, 2, 4)
NUM_CLIENTS = 8 if SMOKE else 64
ONLINE = 0.5 if SMOKE else 0.25          # k = 4 smoke / 16 full
BATCH = 8 if SMOKE else 32
K_LOCAL = 2 if SMOKE else 10
DIM = 16 if SMOKE else 256
ROUNDS = 3 if SMOKE else 20
SETTLE = 0 if SMOKE else 2
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build(shards: int) -> FederatedTrainer:
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=DIM,
                        batch_size=BATCH, data_plane="stream"),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            online_client_rate=ONLINE, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=K_LOCAL),
        mesh=MeshConfig(client_shards=shards),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=BATCH)
    return FederatedTrainer(cfg, model, make_algorithm(cfg),
                            data.train)


def run_arm(shards: int):
    """One shard arm: warmup trace + settle, then ROUNDS timed rounds
    under the sentinel. Returns (per-round rows, summary, params)."""
    tr = build(shards)
    server, clients = tr.init_state(jax.random.key(0))
    server, clients, m = tr.run_round(server, clients)
    sync(server.params)
    jax.device_get(tr.round_scalars_dev(clients, m))
    for _ in range(SETTLE):
        server, clients, m = tr.run_round(server, clients)
        jax.device_get(tr.round_scalars_dev(clients, m))
    rows = []
    with RecompilationSentinel() as sentinel:
        t0 = time.perf_counter()
        for r in range(ROUNDS):
            r0 = time.perf_counter()
            server, clients, m = tr.run_round(server, clients)
            sync(server.params)
            dt = time.perf_counter() - r0
            sc = jax.device_get(tr.round_scalars_dev(clients, m))
            n = max(float(sc["n_online"]), 1.0)
            rows.append({"round": r, "round_s": dt,
                         "loss": float(sc["loss_sum"]) / n,
                         "acc": float(sc["acc_sum"]) / n,
                         "comm_bytes": float(sc["comm_bytes"])})
        total = time.perf_counter() - t0
    retraces = sum(sentinel.counts.values())
    gauges = tr.telemetry_gauges()
    params = jax.device_get(server.params)
    tr.invalidate_stream()
    k = tr.k_dispatch
    rps = ROUNDS / total
    summary = {
        "client_shards": shards,
        "k_dispatch": int(k),
        "ms_per_round": total / ROUNDS * 1e3,
        "rounds_per_s": rps,
        "clients_per_s": k * rps,
        "retraces_during_timed_rounds": retraces,
        "cohort_allreduce_bytes": gauges.get("cohort_allreduce_bytes",
                                             0.0),
        "stream_shard_pack_s": gauges.get("stream_shard_pack_s", 0.0),
    }
    return rows, summary, gauges, params


def write_run_dir(path: str, rows, meta: dict, gauges: dict):
    """The compare-able artifact (fedtorch_tpu.metrics/v1, the same
    shape `fedtorch-tpu summarize/compare` reads for every bench)."""
    os.makedirs(path, exist_ok=True)
    keep = {k: float(v) for k, v in gauges.items()
            if k in ("client_shards", "cohort_allreduce_bytes",
                     "stream_shard_pack_s", "stream_shard_rows")}
    with open(os.path.join(path, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"schema": "fedtorch_tpu.metrics/v1",
                            "created_unix": time.time(),
                            "run": meta}) + "\n")
        for row in rows:
            f.write(json.dumps(dict(row, **keep)) + "\n")


def main():
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform}")
    out = {
        "platform": f"{len(devs)} x {devs[0].device_kind}",
        "config": {"num_clients": NUM_CLIENTS, "online": ONLINE,
                   "batch": BATCH, "K": K_LOCAL, "dim": DIM,
                   "rounds_timed": ROUNDS, "smoke": SMOKE,
                   "data_plane": "stream", "shard_sweep": []},
        "shards": {},
    }
    seed_path = os.path.join(REPO, "MULTICHIP_r05.json")
    if os.path.exists(seed_path):
        with open(seed_path) as f:
            out["seed_point"] = json.load(f)
    finals = {}
    best = None
    n_dev = len(devs)
    # probe k once (S=1 always admissible) for the divisibility filter
    k_probe = build(1).k_dispatch
    sweep = [s for s in SHARD_SWEEP
             if n_dev % s == 0 and k_probe % s == 0]
    out["config"]["shard_sweep"] = sweep
    for shards in sweep:
        log(f"--- client_shards={shards}")
        rows, summary, gauges, params = run_arm(shards)
        finals[shards] = params
        # finals hold HOST numpy (device_get in run_arm) — no device
        # sync; the parity bar is bitwise against the S=1 twin
        diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree.leaves(finals[sweep[0]]),
                                 jax.tree.leaves(finals[shards]))]
        summary["parity_bitwise_vs_one_shard"] = max(diffs) == 0.0
        out["shards"][str(shards)] = summary
        log(f"    {summary['ms_per_round']:.2f} ms/round  "
            f"{summary['clients_per_s']:.1f} clients/s  "
            f"retraces={summary['retraces_during_timed_rounds']}  "
            f"bitwise={summary['parity_bitwise_vs_one_shard']}")
        best = (rows, summary, gauges)  # largest S wins the run dir
    if best is not None:
        runs_dir = os.environ.get("PODSCALE_RUNS_DIR") or os.path.join(
            REPO, "artifacts", "podscale_northstar")
        write_run_dir(runs_dir, best[0],
                      dict(out["config"],
                           client_shards=best[1]["client_shards"],
                           platform=out["platform"]),
                      best[2])
        log(f"run dir: {runs_dir}")
    out["ok"] = all(
        s["parity_bitwise_vs_one_shard"]
        and s["retraces_during_timed_rounds"] == 0
        for s in out["shards"].values())
    path = os.environ.get("PODSCALE_AB_PATH") or os.path.join(
        REPO, "PODSCALE_AB.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    log(f"wrote {path}")
    return 0 if out["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
