"""Execute the full algorithm zoo ON THE REAL TPU (single chip).

`__graft_entry__.dryrun_multichip` validates the sharded program on the
virtual CPU mesh; this is its real-hardware counterpart: every
aggregation family, wire format, and engine hook compiles through the
actual TPU toolchain (mosaic/XLA-TPU) and executes one round on the
chip. Catches real-lowering-only failures (e.g. the scoped-VMEM OOM the
pallas quantize kernel hit at 2M elements).

Also covers engine and model families the MLP-only dryrun matrix does
not: the char-GRU (shakespeare workload, explicit carry), the
transformer LM, bf16 ResNet-20 (the north-star arch), the non-federated
local-SGD engine (`LocalSGDTrainer.fit`), and both sequence-parallel
attention strategies on a 1-chip mesh.

Writes TPU_ZOO.json; prints one JSON line. Exits non-zero without a
TPU (before running anything) and when any case failed.
"""
from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from __graft_entry__ import _run_zoo_case, _zoo_configs  # noqa: E402


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def _mean_online_loss(metrics) -> float:
    """Per-online-client mean train loss — the one loss definition every
    case in this artifact reports."""
    return float(metrics.train_loss.sum()
                 / max(float(metrics.online_mask.sum()), 1.0))


def _model_cases():
    """(name, cfg-builder) cases beyond the MLP zoo matrix."""
    import jax

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    import numpy as np

    def run(arch, feats, labels, *, dataset, dtype="float32", C=4, B=4,
            model_kw=None, mesh_kw=None, seq=None):
        parts = [np.arange(i * len(feats) // C, (i + 1) * len(feats) // C)
                 for i in range(C)]
        data = stack_partitions(feats, labels, parts)
        mkw = dict(model_kw or {})
        if seq:
            mkw["rnn_seq_len"] = seq
        cfg = ExperimentConfig(
            data=DataConfig(dataset=dataset, batch_size=B),
            federated=FederatedConfig(federated=True, num_clients=C,
                                      online_client_rate=1.0,
                                      algorithm="fedavg",
                                      sync_type="local_step"),
            model=ModelConfig(arch=arch, **mkw),
            optim=OptimConfig(lr=0.05, in_momentum=True),
            train=TrainConfig(local_step=2),
            mesh=MeshConfig(num_devices=1, compute_dtype=dtype,
                            **(mesh_kw or {})),
        ).finalize()
        model = define_model(cfg, batch_size=B)
        trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
        server, clients = trainer.init_state(jax.random.key(0))
        server, clients, m = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
        return _mean_online_loss(m)

    rng = np.random.RandomState(3)

    def resnet_bf16():
        return run("resnet20",
                   rng.randn(64, 32, 32, 3).astype(np.float32),
                   rng.randint(0, 10, 64), dataset="cifar10",
                   dtype="bfloat16")

    def gru_shakespeare():
        # shakespeare-shaped: int char ids, next-char targets
        x = rng.randint(0, 86, (64, 50)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        return run("rnn", x, y, dataset="shakespeare", dtype="bfloat16",
                   seq=50)

    def transformer_lm():
        x = rng.randint(0, 86, (64, 64)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        return run("transformer", x, y, dataset="shakespeare",
                   dtype="bfloat16", seq=64,
                   model_kw={"mlp_num_layers": 2,
                             "rnn_hidden_size": 32})

    def local_sgd():
        # the non-federated data-parallel engine (distributed.py mode):
        # two steps-per-sync rounds through LocalSGDTrainer.fit
        from fedtorch_tpu.parallel import build_local_sgd

        cfg = ExperimentConfig(
            data=DataConfig(dataset="cifar10", batch_size=4),
            federated=FederatedConfig(federated=False, num_clients=4),
            model=ModelConfig(arch="cnn"),
            optim=OptimConfig(lr=0.05, in_momentum=True),
            train=TrainConfig(local_step=2, num_epochs=1),
            mesh=MeshConfig(num_devices=1, compute_dtype="bfloat16"),
        ).finalize()
        feats = rng.randn(64, 32, 32, 3).astype(np.float32)
        labels = rng.randint(0, 10, 64)
        model = define_model(cfg, batch_size=4)
        trainer = build_local_sgd(cfg, model, feats, labels)
        _, _, history = trainer.fit(jax.random.key(0))
        return _mean_online_loss(history[-1])

    def seqpar_single_chip():
        # both sequence-parallel strategies lower through the real TPU
        # toolchain (1-chip mesh: the collectives become no-ops but the
        # shard_map program still compiles on mosaic/XLA-TPU); same
        # check as the CPU-mesh dryrun, on real hardware
        from __graft_entry__ import _run_sequence_parallel

        return _run_sequence_parallel(1, label="tpu_zoo(1)")

    def transformer_flash_moe():
        # flash-attention kernel + sparse-MoE dispatch + Switch aux loss
        # through the engine on the real chip, bf16
        x = rng.randint(0, 86, (64, 64)).astype(np.int32)
        y = np.roll(x, -1, axis=1).astype(np.int32)
        return run("transformer", x, y, dataset="shakespeare",
                   dtype="bfloat16", seq=64,
                   model_kw={"mlp_num_layers": 2, "rnn_hidden_size": 32,
                             "attention": "flash", "moe_experts": 4,
                             "moe_capacity_factor": 1.25,
                             "moe_aux_weight": 0.01})

    def resnet_remat_bf16():
        # per-block rematerialization through the real backward pass
        return run("resnet20",
                   rng.randn(64, 32, 32, 3).astype(np.float32),
                   rng.randint(0, 10, 64), dataset="cifar10",
                   dtype="bfloat16", mesh_kw={"remat": True})

    def resnet_matmulconv_bf16():
        # the im2col batched-matmul conv lowering through the real MXU
        # (models/common.py MatmulConv — the MFU lever; mfu_sweep times
        # it, this proves lowering + a finite training round)
        return run("resnet20",
                   rng.randn(64, 32, 32, 3).astype(np.float32),
                   rng.randint(0, 10, 64), dataset="cifar10",
                   dtype="bfloat16",
                   model_kw={"conv_impl": "matmul"})

    def batched_rounds():
        # the single-dispatch scan driver (bench fast path) on the chip
        parts = [np.arange(i * 16, (i + 1) * 16) for i in range(4)]
        feats = rng.randn(64, 20).astype(np.float32)
        labels = rng.randint(0, 10, 64)
        data = stack_partitions(feats, labels, parts)
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=20,
                            batch_size=8),
            federated=FederatedConfig(federated=True, num_clients=4,
                                      online_client_rate=1.0,
                                      algorithm="fedavg",
                                      sync_type="local_step"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.05),
            train=TrainConfig(local_step=2),
            mesh=MeshConfig(num_devices=1),
        ).finalize()
        model = define_model(cfg, batch_size=8)
        trainer = FederatedTrainer(cfg, model, make_algorithm(cfg), data)
        server, clients = trainer.init_state(jax.random.key(0))
        server, clients, ms = trainer.run_rounds(server, clients, 3)
        jax.block_until_ready(server.params)
        return float(ms.train_loss[-1].sum()
                     / max(float(ms.online_mask[-1].sum()), 1.0))

    return [("resnet20_bf16", resnet_bf16, "loss"),
            ("rnn_gru_bf16", gru_shakespeare, "loss"),
            ("transformer_bf16", transformer_lm, "loss"),
            ("transformer_flash_moe_bf16", transformer_flash_moe, "loss"),
            ("resnet20_remat_bf16", resnet_remat_bf16, "loss"),
            ("resnet20_matmulconv_bf16", resnet_matmulconv_bf16,
             "loss"),
            ("batched_rounds_scan", batched_rounds, "loss"),
            ("local_sgd_cnn_bf16", local_sgd, "loss"),
            ("seqpar_1chip", seqpar_single_chip, "err")]


def main():
    from fedtorch_tpu.utils import require_tpu
    device = require_tpu("tpu_zoo_check.py")
    log(f"device: {device}")
    results = {"device": device, "cases": {}}
    ok = True

    # ZOO_ONLY=substr[,substr...]: run only matching cases and MERGE
    # them into the existing artifact (all_ok recomputed over the
    # merged set). Lets a targeted fix re-validate one case in minutes
    # of chip time instead of re-running the full zoo.
    only = [s for s in os.environ.get("ZOO_ONLY", "").split(",") if s]

    def selected(name: str) -> bool:
        return not only or any(s in name for s in only)

    for name, fed_kw, trainer_kw in _zoo_configs(1):
        if not selected(name):
            continue
        t0 = time.time()
        try:
            m = _run_zoo_case(name, fed_kw, trainer_kw, 1)
            loss = _mean_online_loss(m)
            finite = loss == loss and abs(loss) != float("inf")
            results["cases"][name] = {
                "ok": bool(finite), "loss": round(loss, 4),
                "secs": round(time.time() - t0, 1)}
            ok &= finite
            log(f"{name}: loss {loss:.4f} ({time.time()-t0:.1f}s)")
        except Exception as e:
            results["cases"][name] = {"ok": False,
                                      "error": str(e)[:300]}
            ok = False
            log(f"{name}: FAIL {str(e)[:200]}")

    for name, fn, kind in _model_cases():
        if not selected(name):
            continue
        t0 = time.time()
        try:
            val = fn()
            finite = val == val and abs(val) != float("inf")
            # "err" cases measure a numerical error bound (seqpar vs the
            # dense oracle), not a training loss — keep full precision
            rec = {"ok": bool(finite),
                   kind: round(val, 4) if kind == "loss" else val,
                   "secs": round(time.time() - t0, 1)}
            results["cases"][name] = rec
            ok &= finite
            log(f"{name}: {kind} {val:.4g} ({time.time()-t0:.1f}s)")
        except Exception as e:
            results["cases"][name] = {"ok": False,
                                      "error": str(e)[:300]}
            ok = False
            log(f"{name}: FAIL {str(e)[:200]}")

    if only and not results["cases"]:
        # a pattern that selects nothing must not write a vacuously
        # green artifact
        log(f"ZOO_ONLY={','.join(only)} matched no cases — not writing")
        print(json.dumps({"tpu_zoo_ok": False, "skipped": True,
                          "device": device}))
        return 1

    if only:
        # partial run: merge into the prior artifact; all_ok reflects
        # the MERGED case set so one green re-run can't mask other
        # failures (and vice versa). Refuse when there is no prior
        # artifact to merge into.
        prior = None
        if os.path.exists("TPU_ZOO.json"):
            with open("TPU_ZOO.json") as f:
                prior = json.load(f)
        if prior is None:
            log("ZOO_ONLY needs a prior TPU_ZOO.json to merge into — "
                "run the full zoo first; not writing")
            print(json.dumps({"tpu_zoo_ok": False, "skipped": True,
                              "device": device}))
            return 1
        merged = dict(prior.get("cases", {}))
        merged.update(results["cases"])
        updated = sorted(results["cases"])
        results["cases"] = merged
        results["partial_update"] = {
            "cases": updated,
            "at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())}
        ok = all(c.get("ok") for c in merged.values())

    results["all_ok"] = bool(ok)
    results["note"] = ("single-chip execution of every zoo case; the "
                       "sharded multi-device program is covered by "
                       "dryrun_multichip on the virtual CPU mesh")
    with open("TPU_ZOO.json", "w") as f:
        json.dump(results, f, indent=1)
    print(json.dumps({"tpu_zoo_ok": ok,
                      "n_cases": len(results["cases"]),
                      "device": device}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
