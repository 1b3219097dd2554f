"""The BASELINE.json benchmark-config suite, runnable on one command.

BASELINE.json lists five benchmark config families the new framework is
expected to cover. This script runs ALL of them end-to-end — real
partitioners, real round programs, real models at the stated scales —
and writes BASELINE_SUITE.json with per-case throughput and learning
trajectories:

1. FedAvg · MNIST shapes · LeNet-style CNN · 10 clients IID
2. FedAvg + FedProx · CIFAR-10 shapes · ResNet-20 · 100 clients,
   Dirichlet non-IID
3. SCAFFOLD + FedGATE · CIFAR-10 shapes · ResNet-20 (control-variate /
   gradient-tracking sync)
4. FedCOMGATE (int8) + Qsparse (top-k, error feedback) · compressed
   aggregation at the same CIFAR scale
5. APFL + DRFA · EMNIST shapes (emnist_full, 62-way) · MLP
   (personalized + distributionally-robust minimax)

No network here: datasets are class-conditional Gaussian synthetics
at the exact shapes/dtypes of the named datasets; every other component
— partitioner, engine, algorithm, eval — is the production path.

TPU only: exits non-zero without a chip, and non-zero when any case
failed (the failure is still recorded in the output file).

Usage:
    python scripts/baseline_suite.py [--smoke] [--cases 1,3,5]
    (--smoke shrinks shapes for a quick check on the chip)
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


def build_case(name, *, dataset, shape, classes, arch, clients, alg,
               batch, local_steps, rate, rounds, partition="iid",
               n_per_client=200, momentum=True, **fed_kw):
    return dict(name=name, dataset=dataset, shape=shape, classes=classes,
                arch=arch, clients=clients, alg=alg, batch=batch,
                local_steps=local_steps, rate=rate, rounds=rounds,
                partition=partition, n_per_client=n_per_client,
                momentum=momentum, fed_kw=fed_kw)


def cases(smoke: bool):
    cif = dict(dataset="cifar10", shape=(32, 32, 3), classes=10,
               arch="resnet20", clients=10 if smoke else 100,
               batch=8 if smoke else 50, local_steps=2 if smoke else 10,
               rate=0.5 if smoke else 0.1, rounds=2 if smoke else 8,
               partition="dirichlet", n_per_client=24 if smoke else 200)
    emn = dict(dataset="emnist_full", shape=(28, 28, 1), classes=62,
               arch="mlp", clients=8 if smoke else 30,
               batch=8 if smoke else 32, local_steps=2 if smoke else 10,
               rate=1.0, rounds=2 if smoke else 15, partition="label",
               n_per_client=32 if smoke else 150)
    return [
        build_case("1_fedavg_mnist_cnn_iid", dataset="mnist",
                   shape=(28, 28, 1), classes=10, arch="cnn",
                   clients=10, alg="fedavg", batch=8 if smoke else 50,
                   local_steps=2 if smoke else 10, rate=1.0,
                   rounds=2 if smoke else 20, partition="iid",
                   n_per_client=32 if smoke else 300),
        build_case("2a_fedavg_cifar_resnet20_dirichlet", alg="fedavg",
                   **cif),
        build_case("2b_fedprox_cifar_resnet20_dirichlet", alg="fedprox",
                   **cif),
        # control-variate updates assume plain SGD (see scaffold.py note)
        build_case("3a_scaffold_cifar_resnet20", alg="scaffold",
                   momentum=False, **cif),
        build_case("3b_fedgate_cifar_resnet20", alg="fedgate",
                   momentum=False, **cif),
        build_case("4a_fedcomgate_int8", alg="fedgate", momentum=False,
                   quantized=True, quantized_bits=8, **cif),
        build_case("4b_qsparse_topk", alg="qsparse", momentum=False,
                   compressed=True, compressed_ratio=0.25, **cif),
        build_case("5a_apfl_emnist_mlp", alg="apfl", personal=True,
                   personal_alpha=0.5, **emn),
        build_case("5b_drfa_emnist_mlp", alg="fedavg", drfa=True,
                   drfa_gamma=0.1, **emn),
    ]


def synth_data(shape, classes, n_total, n_test, seed):
    import numpy as np
    rng = np.random.RandomState(seed)
    means = rng.randn(classes, *shape).astype("float32") * 0.8
    y = rng.randint(0, classes, n_total)
    x = means[y] + rng.randn(n_total, *shape).astype("float32")
    ty = rng.randint(0, classes, n_test)
    tx = means[ty] + rng.randn(n_test, *shape).astype("float32")
    return x, y, tx, ty


def run_case(c, dtype):
    import numpy as np
    import jax

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions, \
        train_val_split
    from fedtorch_tpu.data.partition import (
        dirichlet_partition, iid_partition, label_sorted_partition,
    )
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, evaluate
    # timed drains sync through scripts/bench_timing.py's one rule
    from fedtorch_tpu.utils.tracing import fetch_sync

    C = c["clients"]
    x, y, tx, ty = synth_data(c["shape"], c["classes"],
                              C * c["n_per_client"], 512, seed=11)
    if c["partition"] == "dirichlet":
        parts = dirichlet_partition(y, C, concentration=0.5, seed=1)
        parts = [p for p in parts if len(p)]
    elif c["partition"] == "label":
        parts = label_sorted_partition(y, C, num_class_per_client=4,
                                       seed=1)
    else:
        parts = iid_partition(len(y), C, seed=1)

    fed_kw = dict(c["fed_kw"])
    personal = fed_kw.pop("personal", False)
    cfg = ExperimentConfig(
        data=DataConfig(dataset=c["dataset"], batch_size=c["batch"]),
        federated=FederatedConfig(
            federated=True, num_clients=len(parts),
            online_client_rate=c["rate"], algorithm=c["alg"],
            sync_type="local_step", personal=personal, **fed_kw),
        model=ModelConfig(arch=c["arch"], mlp_hidden_size=200),
        optim=OptimConfig(lr=0.1, in_momentum=c["momentum"],
                          weight_decay=0.0),
        train=TrainConfig(local_step=c["local_steps"]),
        mesh=MeshConfig(compute_dtype=dtype),
    ).finalize()
    val = None
    if personal:
        parts, vparts = train_val_split(parts, 0.2, seed=2)
        val = stack_partitions(x, y, vparts)
    data = stack_partitions(x, y, parts)
    model = define_model(cfg, batch_size=c["batch"])
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data,
                               val_data=val)
    server, clients = trainer.init_state(jax.random.key(0))

    t0 = time.time()
    server, clients, m = trainer.run_round(server, clients)
    fetch_sync(server.params)
    compile_s = time.time() - t0
    first_loss = float(m.train_loss.sum() / m.online_mask.sum())

    t0 = time.time()
    for _ in range(c["rounds"] - 1):
        server, clients, m = trainer.run_round(server, clients)
    fetch_sync(server.params)
    dt = max(time.time() - t0, 1e-9)
    n_chips = int(trainer.mesh.devices.size)
    steps = (c["rounds"] - 1) * trainer.k_online * trainer.local_steps
    last_loss = float(m.train_loss.sum() / m.online_mask.sum())
    res = evaluate(model, server.params, tx, ty, batch_size=256)
    return {
        "ok": bool(np.isfinite(last_loss)),
        "clients": len(parts),
        "steps_per_sec_per_chip": round(steps / dt / n_chips, 2),
        "compile_plus_first_round_s": round(compile_s, 1),
        "first_round_loss": round(first_loss, 4),
        "last_round_loss": round(last_loss, 4),
        "test_top1_after": round(float(res.top1), 4),
        "rounds": c["rounds"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cases", default=None,
                    help="comma-separated case-name prefixes (1,2a,...)")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from fedtorch_tpu.utils import enable_compile_cache, require_tpu
    device = require_tpu("baseline_suite.py")
    enable_compile_cache()

    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    log(f"device: {device}  compute dtype: {dtype}")

    want = args.cases.split(",") if args.cases else None
    out = {"device": device,
           "smoke": args.smoke,
           "note": ("class-conditional synthetic shards at the named "
                    "datasets' exact shapes"),
           "cases": {}}
    for c in cases(args.smoke):
        if want and not any(c["name"].startswith(w) for w in want):
            continue
        log(f"--- {c['name']} ---")
        t0 = time.time()
        try:
            out["cases"][c["name"]] = run_case(c, dtype)
            log(f"{c['name']}: {out['cases'][c['name']]}")
        except Exception as e:  # record the failure, keep the suite going
            out["cases"][c["name"]] = {"ok": False, "error": repr(e)[:300]}
            log(f"{c['name']}: FAILED {e!r}")
        log(f"({time.time() - t0:.0f}s)")
    path = args.out or os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BASELINE_SUITE.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    n_ok = sum(1 for v in out["cases"].values() if v.get("ok"))
    print(json.dumps({"cases_ok": n_ok,
                      "cases_total": len(out["cases"]),
                      "device": device}), flush=True)
    return 0 if out["cases"] and n_ok == len(out["cases"]) else 1


if __name__ == "__main__":
    sys.exit(main())
