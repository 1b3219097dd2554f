"""Head-to-head baseline reproduction vs the reference (BASELINE.md
procedure: reproduce the reference run configs numerically, then compare
wall-clock).

Runs the reference's OWN centered-mode implementation (torch, from
/root/reference, with minimal torch-2.x compatibility shims) and
fedtorch_tpu with the matched configuration on the IDENTICAL dataset (the
reference's generated synthetic shards are loaded directly), then prints
an accuracy/wall-clock table.

Usage:  python scripts/compare_reference.py [--rounds 10] [--algos ...]
Needs /root/reference mounted; runs offline (synthetic data only).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REF = "/root/reference"
WORKDIR = "/tmp/fedtorch_compare"
OUT_JSON = os.path.join(REPO, "COMPARE_REFERENCE.json")

COMPARE_SCHEMA = "fedtorch_tpu.compare_reference/v1"
# the head-to-head acceptance band: ours must land within this many
# accuracy points of the reference on the SAME data + config (the
# BASELINE.md reproduction bar)
ACC_TOLERANCE_PTS = 5.0


def build_payload(rows: dict, rounds: int) -> dict:
    """The machine-checkable head-to-head record (VERDICT item 8):
    per-algorithm ``{ref_acc, ours_acc, ref_wall, ours_wall,
    speedup}`` — accuracies are final TEST top-1 in percent on the
    identical reference-generated shards, walls are seconds for the
    same number of rounds."""
    return {
        "schema": COMPARE_SCHEMA,
        "rounds": rounds,
        "acc_tolerance_pts": ACC_TOLERANCE_PTS,
        "algorithms": rows,
    }


def validate_payload(payload: dict) -> None:
    """Raise ``ValueError`` on schema violations or an accuracy delta
    outside the tolerance band — the test's entry point, so the claim
    "head-to-head parity" stays machine-checkable instead of a table
    in a log."""
    if payload.get("schema") != COMPARE_SCHEMA:
        raise ValueError(
            f"schema {payload.get('schema')!r} != {COMPARE_SCHEMA!r}")
    algos = payload.get("algorithms")
    if not isinstance(algos, dict) or not algos:
        raise ValueError("payload carries no per-algorithm rows")
    tol = float(payload.get("acc_tolerance_pts", ACC_TOLERANCE_PTS))
    for name, row in algos.items():
        for key in ("ref_acc", "ours_acc", "ref_wall", "ours_wall",
                    "speedup"):
            v = row.get(key)
            if not isinstance(v, (int, float)) or isinstance(v, bool):
                raise ValueError(
                    f"{name}: field {key!r} must be numeric, got {v!r}")
        if row["ref_wall"] <= 0 or row["ours_wall"] <= 0:
            raise ValueError(f"{name}: non-positive wall time")
        expect = row["ref_wall"] / row["ours_wall"]
        if abs(row["speedup"] - expect) > 1e-6 * max(expect, 1.0):
            raise ValueError(
                f"{name}: speedup {row['speedup']} != ref_wall/"
                f"ours_wall ({expect})")
        delta = abs(row["ref_acc"] - row["ours_acc"])
        if delta > tol:
            raise ValueError(
                f"{name}: |ref_acc - ours_acc| = {delta:.2f}pts "
                f"exceeds the {tol}pt tolerance")


def install_reference_shims():
    """Make the torch-1.6-era reference run under torch 2.x on one core."""
    for name in ("torchvision", "torchvision.datasets",
                 "torchvision.transforms"):
        sys.modules.setdefault(name, types.ModuleType(name))
    sys.modules["torchvision"].datasets = sys.modules[
        "torchvision.datasets"]
    sys.modules["torchvision"].transforms = sys.modules[
        "torchvision.transforms"]
    sys.path.insert(0, REF)

    import torch
    import torch.utils.data as tud

    class _DL(tud.DataLoader):  # single-process loaders on a 1-core host
        def __init__(self, *a, **kw):
            kw["num_workers"] = 0
            kw["pin_memory"] = False
            super().__init__(*a, **kw)

    tud.DataLoader = _DL
    torch.utils.data.DataLoader = _DL
    # torch>=2 zero_grad defaults to set_to_none=True; the reference
    # mutates .grad.data in place and needs zeroed tensors
    _zero = torch.optim.Optimizer.zero_grad
    torch.optim.Optimizer.zero_grad = \
        lambda self, set_to_none=False: _zero(self, set_to_none=False)

    # .view on non-contiguous slices + formatting 1-elem tensors
    import fedtorch.components.metrics as M

    def _accuracy(output, target, topk=(1,), rnn=False):
        if rnn:
            output = output.permute(0, 2, 1).reshape(-1, output.size(1))
            target = target.reshape(-1)
        maxk = max(topk)
        batch_size = target.size(0)
        _, pred = output.topk(maxk, 1, True, True)
        pred = pred.t()
        correct = pred.eq(target.view(1, -1).expand_as(pred))
        return [correct[:k].contiguous().reshape(-1).float().sum(0)
                .mul_(100.0 / batch_size) for k in topk]

    M.accuracy = _accuracy

    # The reference's centered main CALLS qffl_aggregation_centered
    # (centered/main.py:206) but never imports it (main.py:18-22 pulls
    # only fedavg/fedgate/scaffold/qsparse) — its own qFFL entry path
    # crashes with NameError. Inject the function it meant to import
    # (defined at comms/algorithms/federated/centered/qffl.py:4) so
    # the comparison can still run the reference as intended.
    import fedtorch.comms.trainings.federated.centered.main as ref_main_mod
    if not hasattr(ref_main_mod, "qffl_aggregation_centered"):
        from fedtorch.comms.algorithms.federated.centered.qffl import \
            qffl_aggregation_centered
        ref_main_mod.qffl_aggregation_centered = qffl_aggregation_centered


def reference_argv(algo: str, rounds: int, extra=()):
    argv = [
        "main_centered.py", "--federated", "True",
        "--federated_type", algo if algo != "drfa" else "fedavg",
        "--data", "synthetic", "--data_dir", f"{WORKDIR}/data",
        "--num_comms", str(rounds), "--online_client_rate", "1.0",
        "--federated_sync_type", "local_step", "--local_step", "5",
        "--arch", "logistic_regression", "--lr", "0.1",
        "--batch_size", "20", "--weight_decay", "0.0001",
        "--iid_data", "False", "--num_workers", "4",
        "--on_cuda", "False", "--debug", "True",
        "--lr_schedule_scheme", "custom_multistep",
        "--checkpoint", f"{WORKDIR}/ckpt",
        "--is_distributed", "False", "--blocks", "4",
        "--manual_seed", "6",
    ]
    if algo == "drfa":
        argv += ["--federated_drfa", "True", "--drfa_gamma", "0.1"]
    if algo == "apfl":
        argv += ["--fed_personal", "True", "--fed_personal_alpha", "0.5"]
    if algo in ("perfedavg", "perfedme"):
        argv += ["--fed_personal", "True"]
    return argv + list(extra)


def run_reference(algo: str, rounds: int):
    import contextlib
    install_reference_shims()
    # the reference's synthetic generator ignores its own seed param and
    # draws from the GLOBAL numpy RNG (federated_datasets.py:204-212);
    # seed it so the generated shards are reproducible & non-degenerate
    import numpy as np
    np.random.seed(20260728)
    sys.argv = reference_argv(algo, rounds)
    from fedtorch.parameters import get_args
    args = get_args()
    from main_centered import main as ref_main
    t0 = time.time()
    with open(f"{WORKDIR}/ref_{algo}.log", "w") as f, \
            contextlib.redirect_stdout(f):
        ref_main(args)
    wall = time.time() - t0
    return wall


def load_reference_data():
    import numpy as np
    import torch
    base = f"{WORKDIR}/data/synthetic/synthetic0.0-0.0"
    cx, cy = [], []
    i = 0
    while os.path.exists(f"{base}/Client_{i}.pt"):
        x, y = torch.load(f"{base}/Client_{i}.pt")
        cx.append(np.asarray(x))
        cy.append(np.asarray(y))
        i += 1
    tx, ty = torch.load(f"{base}/Test.pt")
    return cx, cy, np.asarray(tx), np.asarray(ty)


def run_ours(algo: str, rounds: int, cx, cy, tx, ty,
             use_tpu: bool = False):
    import jax
    if not use_tpu:
        # the comparison is against the reference's CPU loop: run ours
        # on the CPU too unless --tpu asks otherwise
        jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import jax.numpy as jnp
    sys.path.insert(0, REPO)
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
        OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, evaluate

    sizes = [len(y) for y in cy]
    feats, labels = np.concatenate(cx), np.concatenate(cy)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    parts = [np.arange(offs[i], offs[i + 1]) for i in range(len(sizes))]
    val_data = None
    if algo in ("perfedavg", "perfedme"):
        # MAML-style algorithms evaluate on per-client validation
        # batches (needs_val_batch); same 10% split convention as
        # build_federated_data / the reference's random_split
        from fedtorch_tpu.data.batching import train_val_split
        parts, val_parts = train_val_split(parts, 0.1, seed=6)
        val_data = stack_partitions(feats, labels, val_parts)
    data = stack_partitions(feats, labels, parts)

    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=feats.shape[1],
                        batch_size=20),
        federated=FederatedConfig(
            federated=True, num_clients=len(sizes), num_comms=rounds,
            online_client_rate=1.0,
            algorithm=algo if algo != "drfa" else "fedavg",
            drfa=(algo == "drfa"), sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1, weight_decay=1e-4),
        train=TrainConfig(local_step=5),
    ).finalize()
    model = define_model(cfg, batch_size=20)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data,
                               val_data=val_data)
    server, clients = trainer.init_state(jax.random.key(6))
    # compile warmup — TWO rounds, because algorithms with round-0
    # forcing (afl: uniform round 0, lambda-weighted afterwards) jit
    # two distinct round programs; a 1-round warmup left the second
    # compile inside the timed loop (measured: afl rounds 0 AND 1
    # each ~2.3s, rounds 2+ ~1ms)
    s, c, _ = trainer.run_round(server, clients)
    s, c, _ = trainer.run_round(s, c)
    # drain warmup / close the timed segment with a sync, so the
    # clock times execution and not dispatch (scripts/bench_timing.py)
    from bench_timing import sync as bench_sync
    bench_sync(s.params)
    server, clients = trainer.init_state(jax.random.key(6))
    t0 = time.time()
    for _ in range(rounds):
        server, clients, _ = trainer.run_round(server, clients)
    bench_sync(server.params)
    wall = time.time() - t0
    tr = evaluate(model, server.params, feats, labels, batch_size=200)
    te = evaluate(model, server.params, tx, ty, batch_size=200)
    return wall, float(tr.top1) * 100, float(te.top1) * 100


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--algos", nargs="+",
                    default=["fedavg", "scaffold", "fedgate"])
    ap.add_argument("--tpu", action="store_true",
                    help="run ours on the default (TPU) platform")
    args = ap.parse_args()
    os.makedirs(WORKDIR, exist_ok=True)

    def ref_final_metrics(algo):
        import re
        last = {}
        with open(f"{WORKDIR}/ref_{algo}.log") as f:
            for line in f:
                m = re.search(
                    r"(Global performance for train"
                    r"|Global performance for validation|Test)"
                    r" at batch.*Prec@1: ([\d.]+).*Loss: ([\d.]+)",
                    line)
                if m:
                    # personal-eval paths (apfl) log the held-out
                    # metric as "Global performance for validation"
                    # instead of a "Test" line
                    key = "train" if "train" in m.group(1) else "test"
                    last[key] = float(m.group(2))
        return last

    print(f"{'algo':<10} {'ref wall':>9} {'ours wall':>10} {'speedup':>8} "
          f"{'ref tr/te%':>12} {'ours tr/te%':>12}")
    rows = {}
    for algo in args.algos:
        ref_wall = run_reference(algo, args.rounds)
        refm = ref_final_metrics(algo)
        if not refm:
            raise RuntimeError(
                f"reference run for {algo!r} produced no parseable "
                f"metrics — inspect {WORKDIR}/ref_{algo}.log")
        cx, cy, tx, ty = load_reference_data()
        ours_wall, tr, te = run_ours(algo, args.rounds, cx, cy, tx, ty,
                                     use_tpu=args.tpu)
        speedup = ref_wall / max(ours_wall, 1e-9)
        print(f"{algo:<10} {ref_wall:>8.2f}s {ours_wall:>9.2f}s "
              f"{speedup:>7.1f}x "
              f"{refm.get('train', 0):>5.1f}/{refm.get('test', 0):<5.1f} "
              f"{tr:>5.1f}/{te:<5.1f}")
        # some reference eval paths only log a train/validation metric
        # (apfl) — fall back so the row stays comparable like-for-like
        ref_acc = refm.get("test", refm.get("train", 0.0))
        ours_acc = te if "test" in refm else tr
        rows[algo] = {
            "ref_acc": ref_acc, "ours_acc": ours_acc,
            "ref_wall": ref_wall, "ours_wall": ours_wall,
            "speedup": speedup,
            "ref_train_acc": refm.get("train"),
            "ours_train_acc": tr, "ours_test_acc": te,
        }

    payload = build_payload(rows, args.rounds)
    validate_payload(payload)  # fail HERE, not in a later test run
    with open(OUT_JSON, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"wrote {OUT_JSON}")


if __name__ == "__main__":
    main()
