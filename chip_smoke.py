#!/usr/bin/env python3
"""The standing check that the system still starts on the chip.

Drives the main path once — short-flag launcher -> ``fedtorch_tpu.cli.main``
-> ``run_experiment`` -> ``FederatedTrainer`` -> ``round_program`` ->
``_round_core`` — at the full width of the north-star configuration
(BASELINE.json: FedAvg, ResNet-20 on CIFAR-10 shapes, 100 clients,
non-IID Dirichlet, batch 50, K = 10 local steps, 10 % participation,
bf16 compute) for a few rounds with evaluation and a checkpoint each
round, then compiles and runs both Pallas kernels under Mosaic and
checks them against their XLA references. One process, every chip JAX
finds, weights and data made from a seed, no network.

    python3 chip_smoke.py              # the check: needs a TPU
    python3 chip_smoke.py --test-size  # plumbing only, tiny, runs on CPU

Without a TPU the default invocation prints the device header and exits
non-zero before it compiles anything. Any phase that raises makes the
exit code non-zero. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and the line before it (``chip_smoke report: {...}``) carries what was
observed: set-up/compile seconds apart from the steady round wall, the
per-round losses, ``peak_bytes_in_use`` per device, which host-pipeline
implementation ran, and the kernel checks. These are observations of
one run, not benchmark results (the benchmark is ROADMAP Speed 1).
"""
from __future__ import annotations

import argparse
import json
import os
import pickle
import shutil
import statistics
import sys
import tempfile
import time
import traceback

SEED = 0
MOSAIC_CALL = "tpu_custom_call"  # Mosaic's custom-call target in HLO text

# the north-star configuration at full width, and the plumbing size the
# CPU test uses (same code path, nothing in it is a measurement)
FULL = dict(arch="resnet20", clients=100, batch=50, local_steps=10,
            rate=0.1, rounds=5, train_per_batch=10_000, test=10_000,
            quant_clients=20, quant_tiled_elems=2_000_000,
            flash_shapes=((1, 4096, 8, 64), (2, 1024, 8, 64)))
TEST = dict(arch="resnet8", clients=16, batch=8, local_steps=2,
            rate=0.25, rounds=3, train_per_batch=64, test=64,
            quant_clients=8, quant_tiled_elems=70_000,
            flash_shapes=((1, 256, 2, 32), (1, 128, 2, 32)))


def log(*a):
    print(*a, flush=True)


# -- data ----------------------------------------------------------------

def write_cifar10_batches(root: str, seed: int, train_per_batch: int,
                          n_test: int) -> str:
    """A seeded ``cifar-10-batches-py/`` in the loader's pickle format
    (data/datasets.py:load_cifar): 5 train batches and a test batch of
    uint8 ``[N, 3072]`` under ``b"data"`` with ``b"labels"``. Images are
    a per-class template plus noise, so a few rounds of training lower
    the loss. Returns the ``--data_path`` to pass."""
    import numpy as np

    base = os.path.join(root, "cifar-10-batches-py")
    os.makedirs(base, exist_ok=True)
    rng = np.random.default_rng(seed)
    templates = rng.integers(48, 208, size=(10, 3072)).astype(np.float32)

    def batch(n):
        y = rng.integers(0, 10, size=n)
        x = templates[y] + rng.normal(0.0, 40.0, (n, 3072)).astype(
            np.float32)
        return np.clip(x, 0, 255).astype(np.uint8), y

    names = [f"data_batch_{i}" for i in range(1, 6)] + ["test_batch"]
    for name in names:
        n = n_test if name == "test_batch" else train_per_batch
        x, y = batch(n)
        with open(os.path.join(base, name), "wb") as f:
            pickle.dump({b"data": x, b"labels": [int(v) for v in y]}, f)
    return root


# -- phase 1: the north-star run through the normal entry point ----------

def north_star_argv(size: dict, data_dir: str, run_dir: str) -> list:
    """README's north-star command (``-fs local_step`` pins K; ``-lg
    0.1`` is the benchmark configuration's learning rate — the
    launcher's default of 1.0 is the reference's, tuned for its MLPs)."""
    return ["-f", "-ft", "fedavg", "-d", "cifar10", "-a", size["arch"],
            "-n", str(size["clients"]), "-b", str(size["batch"]),
            "-c", str(size["rounds"]), "-fs", "local_step",
            "-l", str(size["local_steps"]), "-k", str(size["rate"]),
            "-lg", "0.1", "-p", data_dir,
            "--dirichlet", "true", "--compute_dtype", "bfloat16",
            "--run_dir", run_dir, "--manual_seed", str(SEED)]


def _spans(run_dir: str) -> dict:
    """``{name: [dur_s by occurrence]}`` from the run's host trace."""
    with open(os.path.join(run_dir, "trace.json")) as f:
        doc = json.load(f)
    events = doc["traceEvents"] if isinstance(doc, dict) else doc
    out: dict = {}
    for e in events:
        if e.get("ph") == "X":
            out.setdefault(e["name"], []).append(e["dur"] / 1e6)
    return out


def _check_checkpoint(run_dir: str, rounds: int) -> dict:
    import jax
    import numpy as np
    from flax import serialization

    from fedtorch_tpu.utils.checkpoint import _unframe_payload

    with open(os.path.join(run_dir, "checkpoint.json")) as f:
        meta = json.load(f)
    assert int(meta["round"]) == rounds, (
        f"checkpoint.json round {meta['round']} != {rounds}")
    with open(os.path.join(run_dir, "checkpoint.ckpt"), "rb") as f:
        payload, why = _unframe_payload(f.read())
    assert why is None, f"checkpoint frame: {why}"
    state = serialization.msgpack_restore(payload)
    leaves = [np.asarray(v)
              for v in jax.tree.leaves(state["server"]["params"])]
    assert leaves and all(np.isfinite(v).all() for v in leaves), \
        "checkpointed server params are not finite"
    return {"round": int(meta["round"]), "bytes": len(payload),
            "server_param_leaves": len(leaves)}


def phase_trainer(size: dict, work: str) -> dict:
    import run_tpu

    t0 = time.time()
    data_dir = write_cifar10_batches(
        os.path.join(work, "data"), SEED, size["train_per_batch"],
        size["test"])
    data_gen_s = time.time() - t0
    run_dir = os.path.join(work, "run")
    t0 = time.time()
    results = run_tpu.main(north_star_argv(size, data_dir, run_dir))
    wall_s = time.time() - t0

    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f][1:]  # [0] is the header
    rounds = size["rounds"]
    assert [r["round"] for r in rows] == list(range(rounds)), rows
    losses = [r["loss"] for r in rows]
    assert all(v == v and abs(v) != float("inf") for v in losses), losses
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"
    round_s = [r["round_s"] for r in rows]
    steady = statistics.median(round_s[1:])
    # the first round compiles; a second round anywhere near it means
    # the program retraced or recompiled
    assert round_s[1] < 0.5 * round_s[0], (
        f"second round {round_s[1]:.2f}s is not far below the first "
        f"{round_s[0]:.2f}s")
    assert all(0.0 <= r["test_top1"] <= 1.0 for r in rows), rows

    with open(os.path.join(run_dir, "program_costs.json")) as f:
        costs = json.load(f)
    flops = {k: v.get("flops") for k, v in costs["programs"].items()}
    assert flops.get("round"), f"no FLOPs for the round program: {costs}"
    assert flops.get("eval"), f"no FLOPs for the eval program: {costs}"

    spans = _spans(run_dir)
    first = {k: round(spans[k][0], 2) for k in
             ("data.build", "round", "scalar_fetch", "cost_capture",
              "eval", "checkpoint") if k in spans}
    return {
        "rounds": rounds,
        "losses": [round(v, 4) for v in losses],
        "test_top1": [round(r["test_top1"], 4) for r in rows],
        "setup": {
            "data_gen_s": round(data_gen_s, 2),
            "first_occurrence_s": first,
            # launch to the end of round 0's row: loader, partitioner,
            # device layout, the round's compile and first execution,
            # the cost-capture twins, the first eval and checkpoint
            "through_round0_s": round(rows[0]["t"] - t0, 2),
        },
        "steady_round_s": {"median": round(steady, 4),
                           "all": [round(v, 4) for v in round_s[1:]]},
        "wall_s": round(wall_s, 2),
        "program_flops": flops,
        "peak_tflops_per_chip": costs["peak_tflops_per_chip"],
        "peak_source": costs["peak_source"],
        "mfu_gauge_last_round": rows[-1].get("model_flops_utilization"),
        "checkpoint": _check_checkpoint(run_dir, rounds),
        "best_top1": results["best_top1"],
    }


# -- phase 2: the int8 wire path (Pallas quantize kernels) ---------------

def _one_bin(got, want, span, bits=8):
    """Kernel-vs-XLA agreement the CPU tests pin (tests/test_pallas.py):
    a reordered statistics sum may move a bin-boundary element by one
    quantization bin, rarely; everything else agrees."""
    import numpy as np

    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    bin_w = max(float(span), 1e-12) / (2 ** bits - 1)
    err = np.abs(got - want)
    assert err.max() < 1.05 * bin_w, (float(err.max()), bin_w)
    flipped = float(np.mean(err > 0.51 * bin_w))
    assert flipped < 1e-3, flipped
    return {"max_err": float(err.max()), "bin": bin_w, "flipped": flipped}


def _quant_trainer(size: dict, on_chip: bool):
    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, MeshConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    C, B = size["quant_clients"], size["batch"]
    cfg = ExperimentConfig(
        data=DataConfig(dataset="cifar10", batch_size=B),
        federated=FederatedConfig(
            federated=True, num_clients=C, online_client_rate=0.5,
            algorithm="fedavg", sync_type="local_step", quantized=True),
        model=ModelConfig(arch=size["arch"]),
        optim=OptimConfig(lr=0.1, in_momentum=True),
        train=TrainConfig(local_step=size["local_steps"]),
        # one-device mesh: on several chips the uplink quantizer is
        # always XLA (algorithms/fedavg.py passes sharded=True)
        mesh=MeshConfig(num_devices=1,
                        compute_dtype="bfloat16" if on_chip
                        else "float32"),
    ).finalize()
    rng = np.random.RandomState(SEED)
    n = 2 * B
    feats = rng.randn(C * n, 32, 32, 3).astype(np.float32)
    labels = rng.randint(0, 10, C * n)
    parts = [np.arange(i * n, (i + 1) * n) for i in range(C)]
    model = define_model(cfg, batch_size=B)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                               stack_partitions(feats, labels, parts))
    server, clients = trainer.init_state(jax.random.key(SEED))
    return trainer, server, clients


def phase_quant(size: dict, on_chip: bool) -> dict:
    """Element level: the three kernels (client-grid batch, single
    block, two-sweep tiled) on ResNet-20-shaped payloads against
    ``ops.quantize.quantize_dequantize``. Round level: a real FedAvg
    ``quantized=True`` round on a one-device mesh, against its twin
    traced with the kernel dispatch forced to XLA. On the chip every
    compiled program must contain the Mosaic custom call."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedtorch_tpu.ops.pallas import quant_kernel as qk
    from fedtorch_tpu.ops.quantize import quantize_dequantize

    # off-chip the same kernel bodies run in the Pallas interpreter
    kw = {} if on_chip else dict(force_pallas=True, interpret=True)
    out: dict = {}

    def compiled_text(fn, *args):
        return jax.jit(fn).lower(*args).compile().as_text()

    def require_mosaic(name, text):
        if on_chip:
            assert MOSAIC_CALL in text, (
                f"{name}: no {MOSAIC_CALL} in the compiled program — "
                "the kernel dispatch took the XLA path")
        out.setdefault("mosaic_custom_call", {})[name] = \
            MOSAIC_CALL in text

    trainer, server, clients = _quant_trainer(size, on_chip)
    params = jax.tree.map(lambda x: np.asarray(x, np.float32),
                          server.params)
    k = trainer.k_online
    rng = np.random.RandomState(SEED + 1)
    down = jax.tree.map(
        lambda p: jnp.asarray(rng.randn(*p.shape).astype(np.float32)),
        params)
    up = jax.tree.map(
        lambda p: jnp.asarray(
            rng.randn(k, *p.shape).astype(np.float32) * 2), params)

    def tree_check(name, tree, leading):
        fn = lambda t: qk.fused_quantize_dequantize_tree(  # noqa: E731
            t, 8, leading_batch=leading, **kw)
        require_mosaic(name, compiled_text(fn, tree))
        got = jax.jit(fn)(tree)
        one = jax.vmap(lambda v: quantize_dequantize(v, 8)) if leading \
            else (lambda v: quantize_dequantize(v, 8))
        want = jax.jit(lambda t: jax.tree.map(one, t))(tree)
        worst = {"max_err": 0.0, "flipped": 0.0}
        for g, w, x in zip(jax.tree.leaves(got), jax.tree.leaves(want),
                           jax.tree.leaves(tree)):
            x = np.asarray(x)
            if leading:
                for c in range(x.shape[0]):
                    r = _one_bin(g[c], w[c], x[c].max() - x[c].min())
                    worst = {m: max(worst[m], r[m]) for m in worst}
            else:
                r = _one_bin(g, w, x.max() - x.min())
                worst = {m: max(worst[m], r[m]) for m in worst}
        out[name] = dict(worst, leaves=len(jax.tree.leaves(tree)))

    tree_check("uplink_batch_kernel", up, leading=True)
    tree_check("downlink_single_block", down, leading=False)

    n = size["quant_tiled_elems"]
    assert n > qk._MAX_VMEM_ELEMS or not on_chip
    big = jnp.asarray(rng.randn(n).astype(np.float32))
    if on_chip:
        tiled = lambda v: qk.fused_quantize_dequantize(v, 8)  # noqa: E731
    else:
        rows = -(-(-(-n // qk._LANE)) // qk._TILE_ROWS) * qk._TILE_ROWS
        pad = jnp.zeros((rows * qk._LANE,), jnp.float32)
        tiled = lambda v: qk._pallas_qdq_tiled(  # noqa: E731
            pad.at[:n].set(v).reshape(rows, qk._LANE),
            jnp.asarray([n], jnp.int32), 8, True).reshape(-1)[:n]
    require_mosaic("tiled_kernel", compiled_text(tiled, big))
    out["tiled_kernel"] = dict(
        _one_bin(jax.jit(tiled)(big),
                 jax.jit(lambda v: quantize_dequantize(v, 8))(big),
                 float(big.max() - big.min())), elems=n)

    # the round: uplink [k]-grid kernel + downlink bucketed kernel
    # inside the jitted round program of a real trainer
    programs, primary = trainer.lowered_cost_programs(server, clients)
    require_mosaic("fedavg_int8_round", programs[primary].compile()
                   .as_text())
    s1, c1, m1 = trainer.run_round(server, clients)
    loss = float(m1.train_loss.sum() / max(float(m1.online_mask.sum()),
                                           1.0))
    assert loss == loss and abs(loss) != float("inf"), loss
    got = jax.device_get(s1.params)
    # the XLA twin: identical trainer, kernel dispatch forced off
    real_on_tpu = qk._on_tpu
    qk._on_tpu = lambda: False
    try:
        t2, server2, clients2 = _quant_trainer(size, on_chip)
        s2, _, _ = t2.run_round(server2, clients2)
        want = jax.device_get(s2.params)
    finally:
        qk._on_tpu = real_on_tpu
    num = sum(float(np.sum((np.asarray(a, np.float64)
                            - np.asarray(b, np.float64)) ** 2))
              for a, b in zip(jax.tree.leaves(got),
                              jax.tree.leaves(want)))
    den = sum(float(np.sum(np.asarray(b, np.float64) ** 2))
              for b in jax.tree.leaves(want))
    rel = (num / max(den, 1e-30)) ** 0.5
    # one-bin flips on a few elements of a one-round delta: far below
    # a percent of the parameter norm
    assert rel < 1e-2, f"int8 round differs from its XLA twin: {rel}"
    out["fedavg_int8_round"] = {"loss": round(loss, 4),
                                "rel_l2_vs_xla_twin": rel,
                                "k_online": int(k)}
    return out


# -- phase 3: flash attention forward and backward -----------------------

def phase_flash(size: dict, on_chip: bool) -> dict:
    """Forward and backward at each shape (T = 4096 is where ``auto``
    selects the kernel, T = 1024 takes the small-block default), f32
    under pinned matmul precision against the XLA oracle to the CPU
    tests' tolerances (tests/test_flash_attention.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedtorch_tpu.ops.pallas.flash_attention import flash_attention

    force = None if on_chip else "interpret"
    out: dict = {}
    for shape in size["flash_shapes"]:
        B, T, H, D = shape
        ks = jax.random.split(jax.random.key(SEED + T), 3)
        q, k, v = (jax.random.normal(kk, shape, jnp.float32) for kk in ks)

        def loss(fn_force):
            def f(q, k, v):
                o = flash_attention(q, k, v, causal=True, force=fn_force)
                return jnp.sum(o.astype(jnp.float32) ** 2) / o.size, o
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))

        with jax.default_matmul_precision("highest"):
            kernel, oracle = loss(force), loss("xla")
            text = kernel.lower(q, k, v).compile().as_text()
            if on_chip:
                assert MOSAIC_CALL in text, (
                    f"flash T={T}: no {MOSAIC_CALL} in the compiled "
                    "program — the dispatch took the dense path")
            (_, o_k), g_k = kernel(q, k, v)
            (_, o_x), g_x = oracle(q, k, v)
        np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_x),
                                   atol=2e-5, rtol=2e-5)
        for a, b in zip(g_k, g_x):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-5, rtol=5e-4)
        out[f"T{T}"] = {
            "shape": list(shape),
            "mosaic_custom_call": MOSAIC_CALL in text,
            "fwd_max_err": float(jnp.max(jnp.abs(o_k - o_x))),
            "bwd_max_err": float(max(jnp.max(jnp.abs(a - b))
                                     for a, b in zip(g_k, g_x))),
        }
    return out


# -- driver --------------------------------------------------------------

def run_phases(phases) -> "tuple[dict, bool]":
    """Run ``[(name, thunk)]`` in order; a phase that raises is recorded
    with its traceback and the rest still run. Returns (report, ok)."""
    report, ok = {}, True
    for name, thunk in phases:
        log(f"--- chip_smoke phase: {name} ---")
        t0 = time.time()
        try:
            report[name] = thunk()
            report[name]["phase_s"] = round(time.time() - t0, 2)
            log(f"--- {name}: ok ({report[name]['phase_s']}s) ---")
        except BaseException as e:  # SystemExit from an entry point too
            ok = False
            traceback.print_exc()
            report[name] = {"error": f"{type(e).__name__}: {e}"[:400],
                            "phase_s": round(time.time() - t0, 2)}
            log(f"--- {name}: FAILED ---")
            if isinstance(e, KeyboardInterrupt):
                break
    return report, ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--test-size", action="store_true",
                    help="test only: tiny sizes, Pallas in interpret "
                         "mode, any platform (plumbing, no device claim)")
    args = ap.parse_args(argv)

    import jax

    from fedtorch_tpu.utils import device_stamp, enable_compile_cache
    device = device_stamp()
    log(f"chip_smoke: jax {jax.__version__} platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}")
    on_chip = device["platform"] == "tpu"
    if not on_chip and not args.test_size:
        print("chip_smoke: needs a TPU; JAX found "
              f"{device['platform']!r}. Refusing before any compile "
              "(--test-size runs the plumbing on CPU).", file=sys.stderr)
        return 1

    from fedtorch_tpu.native import native_available

    size = TEST if args.test_size else FULL
    cache_dir = enable_compile_cache()
    cache_entries = len(os.listdir(cache_dir)) \
        if os.path.isdir(cache_dir) else 0
    work = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        report, ok = run_phases([
            ("trainer", lambda: phase_trainer(size, work)),
            ("quant_kernels", lambda: phase_quant(size, on_chip)),
            ("flash_attention", lambda: phase_flash(size, on_chip)),
        ])
    finally:
        shutil.rmtree(work, ignore_errors=True)
    report.update(
        jax=jax.__version__, device=device,
        test_size=bool(args.test_size),
        native_host_library=bool(native_available()),
        compile_cache={"dir": cache_dir,
                       "entries_at_start": cache_entries},
        peak_bytes_in_use=[
            (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()])
    peaks = report["peak_bytes_in_use"]
    if on_chip and not (all(peaks) and min(peaks) >= 0.25 * max(peaks)):
        # every chip found must have held its share of the client axis
        ok = False
        report["error"] = f"a device held (next to) nothing: {peaks}"
    log("chip_smoke report: " + json.dumps(report, sort_keys=True))
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
