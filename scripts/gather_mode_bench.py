"""Measure gather_mode='batch' vs 'shard' on an actually-sharded mesh.

The 'batch' mode exists to bound cross-device data movement when a round
touches only K*B rows of a much larger client shard
(parallel/federated.py:104-121). On one device XLA fuses both modes into
local HBM gathers, so the win must be measured on a mesh where client
shards live on DIFFERENT devices and ``jnp.take(data.x, idx)`` crosses
them. This script times both modes on the virtual 8-device CPU mesh
(and on whatever real mesh is present if run under a TPU pod) with
K*B << shard size, and writes GATHER_MODE.json.

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python scripts/gather_mode_bench.py
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402
import jax  # noqa: E402

from fedtorch_tpu.utils import enable_compile_cache  # noqa: E402

enable_compile_cache()

from fedtorch_tpu.algorithms import make_algorithm  # noqa: E402
from fedtorch_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data.batching import stack_partitions  # noqa: E402
from fedtorch_tpu.models import define_model  # noqa: E402
# timed drains sync through scripts/bench_timing.py's one rule
from fedtorch_tpu.utils.tracing import fetch_sync  # noqa: E402
from fedtorch_tpu.parallel import FederatedTrainer  # noqa: E402

# K*B = 160 rows touched per round vs 4000-row shards: 'batch' should
# move 4% of what 'shard' moves across devices.
NUM_CLIENTS, BATCH, K, SPC = 32, 16, 10, 4000
FEATURES = 784
ROUNDS = 20


def build(gather_mode: str):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="mnist", batch_size=BATCH),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            online_client_rate=0.25, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch="mlp", mlp_num_layers=2,
                          mlp_hidden_size=256),
        optim=OptimConfig(lr=0.1),
        train=TrainConfig(local_step=K),
        mesh=MeshConfig(),
    ).finalize()
    rng = np.random.RandomState(0)
    feats = rng.randn(NUM_CLIENTS * SPC, FEATURES).astype(np.float32)
    labels = rng.randint(0, 10, NUM_CLIENTS * SPC)
    parts = [np.arange(i * SPC, (i + 1) * SPC)
             for i in range(NUM_CLIENTS)]
    data = stack_partitions(feats, labels, parts)
    model = define_model(cfg, batch_size=BATCH)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data,
                            gather_mode=gather_mode)


def timed(tr) -> tuple[float, float]:
    server, clients = tr.init_state(jax.random.key(0))
    server, clients, _ = tr.run_round(server, clients)
    fetch_sync(server.params)
    t0 = time.time()
    for _ in range(ROUNDS):
        server, clients, _ = tr.run_round(server, clients)
    fetch_sync(server.params)
    dt = (time.time() - t0) / ROUNDS
    loss = float(jax.device_get(
        tr.run_round(server, clients)[2].train_loss).sum())
    return dt, loss


def main():
    devs = jax.devices()
    print(f"devices: {len(devs)} x {devs[0].platform}", file=sys.stderr)
    out = {"platform": f"{len(devs)} x {devs[0].device_kind}",
           "config": {"clients": NUM_CLIENTS, "batch": BATCH, "K": K,
                      "shard_rows": SPC, "touched_rows": K * BATCH},
           "modes": {}}
    for mode in ("shard", "batch"):
        tr = build(mode)
        dt, loss = timed(tr)
        # bytes the data gather moves per round (host arithmetic, for the
        # artifact): k_online clients x rows x feature bytes
        rows = K * BATCH if mode == "batch" else SPC
        moved = tr.k_online * rows * FEATURES * 4
        out["modes"][mode] = {
            "ms_per_round": round(dt * 1e3, 2),
            "data_rows_gathered_per_client": rows,
            "data_mb_gathered_per_round": round(moved / 2**20, 2),
            "final_loss_sum": round(loss, 4),
        }
        print(f"{mode:6s}: {dt*1e3:8.2f} ms/round "
              f"({moved/2**20:.1f} MB data gathered)", file=sys.stderr)
    s, b = (out["modes"]["shard"]["ms_per_round"],
            out["modes"]["batch"]["ms_per_round"])
    out["speedup_batch_vs_shard"] = round(s / b, 2)
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "GATHER_MODE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), file=sys.stderr)


if __name__ == "__main__":
    main()
