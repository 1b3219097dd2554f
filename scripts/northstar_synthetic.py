"""North-star-shaped synthetic repro: FedAvg + ResNet-20, 100 clients.

The container has zero egress, so real CIFAR-10 cannot be staged
(readers accept local files; none exist). This runs the north-star
CONFIG (BASELINE.json: FedAvg, ResNet-20, 100 clients, batch 50, 10
local steps, 10% participation, Dirichlet non-IID) on class-structured
CIFAR-shaped synthetic data, so the full stack — non-IID Dirichlet
partitioner, padded client axis, participation sampling, the jitted
round program, eval — executes at the real scale with a real learning
signal (class-conditional Gaussian images are linearly separable).

Expected trajectories (measured on the v5e, 2026-07-29): plain FedAvg in
this regime — Dirichlet(0.5) label skew, 10 local steps, 10%
participation — exhibits severe client drift: local losses collapse
(clients fit their own few labels) while the server model needs ~50+
rounds to clear the 10% chance floor; full participation reaches ~35%
by round 20; SCAFFOLD's control variates counteract the drift (that is
what they are for — see docs/performance.md).
The engine itself is validated convergent: IID/full-participation hits
~85% in 10 rounds (scripts/../tests convergence smokes). Use
--algorithm scaffold to see the drift-corrected trajectory.

Writes one JSON line to stdout; progress to stderr. Usage:
    python scripts/northstar_synthetic.py [--rounds N] [--smoke]
        [--algorithm fedavg|scaffold|fedgate] [--participation R]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=100)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI")
    ap.add_argument("--algorithm", default="fedavg",
                    choices=["fedavg", "scaffold", "fedgate"])
    ap.add_argument("--participation", type=float, default=0.1)
    ap.add_argument("--target-acc", type=float, default=0.25,
                    help="BASELINE.json's metric is wall-clock to "
                         "target accuracy; report the time this curve "
                         "first crosses this test top-1")
    args = ap.parse_args()

    import numpy as np
    import jax

    from fedtorch_tpu.algorithms import make_algorithm
    # timed drains sync through scripts/bench_timing.py's one rule
    from fedtorch_tpu.utils.tracing import fetch_sync
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.data.partition import dirichlet_partition
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, evaluate

    C = 10 if args.smoke else 100
    B = 8 if args.smoke else 50
    K = 2 if args.smoke else 10
    N_PER = 24 if args.smoke else 200
    log(f"devices: {jax.devices()}")

    # class-conditional Gaussian images: mean pattern per class + noise
    rng = np.random.RandomState(7)
    n_total = C * N_PER
    class_means = rng.randn(10, 32, 32, 3).astype(np.float32) * 0.8
    labels = rng.randint(0, 10, n_total)
    feats = class_means[labels] + rng.randn(
        n_total, 32, 32, 3).astype(np.float32)
    test_labels = rng.randint(0, 10, 1000)
    test_x = class_means[test_labels] + rng.randn(
        1000, 32, 32, 3).astype(np.float32)

    # the real non-IID partitioner (exact-reference Dirichlet scheme)
    parts = dirichlet_partition(labels, C, concentration=0.5, seed=1)
    parts = [p for p in parts if len(p)]  # degenerate-empty guard
    data = stack_partitions(feats, labels, parts)
    log(f"clients: {data.num_clients}, sizes "
        f"min/median/max: {int(np.min(data.sizes))}/"
        f"{int(np.median(data.sizes))}/{int(np.max(data.sizes))}")

    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=B),
        federated=FederatedConfig(
            federated=True, num_clients=data.num_clients,
            online_client_rate=args.participation,
            algorithm=args.algorithm,
            sync_type="local_step"),
        model=ModelConfig(arch="resnet20"),
        # SCAFFOLD/FedGATE control-variate updates assume PLAIN local
        # SGD: (x_s - x_i)/(K*lr) is the mean gradient only without
        # momentum. With in_momentum both the reference and this engine
        # diverge identically (verified side-by-side on the reference's
        # centered scaffold, 2026-07-29) — so momentum is fedavg-only.
        optim=OptimConfig(lr=0.1,
                          in_momentum=(args.algorithm == "fedavg")),
        train=TrainConfig(local_step=K),
        mesh=MeshConfig(compute_dtype=os.environ.get(
            "BENCH_DTYPE", "float32")),
    ).finalize()
    model = define_model(cfg, batch_size=B)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
    server, clients = trainer.init_state(jax.random.key(0))

    # (seconds, test-acc) pairs: `seconds` is cumulative TRAINING time
    # (eval excluded — the metric is wall-clock-to-accuracy of the
    # trainer, and the 10%-of-rounds eval cadence is a measurement
    # choice, not a training cost); `wall_seconds` includes everything.
    curve = []
    train_s = 0.0
    t0 = time.time()
    for r in range(args.rounds):
        t_r = time.time()
        server, clients, metrics = trainer.run_round(server, clients)
        fetch_sync(server.params)
        train_s += time.time() - t_r
        if (r + 1) % max(args.rounds // 10, 1) == 0 or r == 0:
            res = evaluate(model, server.params, test_x, test_labels,
                           batch_size=256)
            curve.append({"round": r + 1,
                          "seconds": round(train_s, 1),
                          "wall_seconds": round(time.time() - t0, 1),
                          "test_top1": round(float(res.top1), 4)})
            log(f"round {r + 1}: test top1 {float(res.top1):.4f} "
                f"({train_s:.0f}s train, "
                f"{time.time() - t0:.0f}s elapsed)")

    # first crossing of the target accuracy, linearly interpolated in
    # (seconds, acc) between the bracketing eval points
    crossing = None
    prev = None
    for pt in curve:
        if pt["test_top1"] >= args.target_acc:
            if prev is not None and prev["test_top1"] < args.target_acc:
                frac = ((args.target_acc - prev["test_top1"])
                        / (pt["test_top1"] - prev["test_top1"]))
                crossing = prev["seconds"] + frac * (
                    pt["seconds"] - prev["seconds"])
            else:
                crossing = pt["seconds"]
            break
        prev = pt
    print(json.dumps({
        "config": f"northstar_synthetic_{args.algorithm}_resnet20",
        "num_clients": data.num_clients, "batch_size": B,
        "local_steps": K, "participation": args.participation,
        "partition": "dirichlet(0.5)",
        "rounds": args.rounds,
        "final_test_top1": curve[-1]["test_top1"] if curve else None,
        "curve": curve,
        "target_acc": args.target_acc,
        "seconds_to_target": (round(crossing, 1)
                              if crossing is not None else None),
        "train_seconds": round(train_s, 1),
        "wall_seconds": round(time.time() - t0, 1),
        "note": "synthetic class-conditional data (zero-egress "
                "container; real CIFAR gated)",
    }), flush=True)


if __name__ == "__main__":
    main()
