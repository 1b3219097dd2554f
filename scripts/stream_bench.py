"""Streaming-data-plane A/B: `data_plane='device'` vs `'stream'`,
plus the SCANNED-STREAM arm (ISSUE 11).

Measures, per plane, on the north-star-shaped workload:

* steady-state round wall-time (fetch-synced — bench_timing.sync);
* bytes moved host→device per round (stream: one packed feed; device:
  zero steady-state — the store is resident, that residency being the
  thing the stream plane trades away);
* device residency (utils.tracing.live_buffer_summary — works on every
  platform — plus device_memory_stats where the allocator reports);
* retraces during the timed window (the recompilation sentinel: the
  streamed round program must trace exactly once, in warmup);
* bitwise parity of the two planes' server params after the A/B.

The acceptance bar (ISSUE 5): steady-state streamed round wall-time
within 10% of device-resident when feed-build+transfer < round compute
— i.e. the round-ahead prefetch actually hides the transfer.

The scanned-stream arm (`run_rounds` on the stream plane — the
round-program builder's feed x scan cell) times window sizes
R in {1, 4, 16}: the producer packs an [R, k, K·B, ...] feed window
while the device scans the previous one, so the stream plane gets the
single-dispatch lever on top of the producer overlap. Each window row
records per-round wall-time, the retrace count (must be 0 past the
one warmup trace per R) and bitwise parity against the DEVICE plane's
scan of the same round sequence; the headline ratios are
`stream_scan_over_stream` (scan must beat per-round stream) and
`stream_scan_over_device_walltime` (the stream-vs-device gap the scan
lever exists to close).

Writes STREAM_AB.json (STREAM_AB_PATH overrides, for the test smoke).
STREAM_BENCH_SMOKE=1 shrinks the workload for CPU CI;
STREAM_BENCH_ARCH overrides the model (e.g. `mlp` for a CPU-feasible
full-population capture — the resnet20 default is the on-chip
`stream` capture-step workload).

THE POPULATION-SCALING ARM (`STREAM_BENCH_POPULATION=1`) replaces the
plane A/B with the million-client drill (docs/performance.md "The
million-client store"): for C in {10^3, 10^5, 10^6} it materializes a
synthetic population to the sharded on-disk store (MmapStoreWriter,
chunked — the 10^6 population never exists in RAM), runs the stream
plane with `data.store='mmap'` + `participation_mode='sparse'` at a
FIXED online cohort k, and records steady round wall, retrace count
and the store-residency gauges. Acceptance: round wall flat in C
(10^6 within 10% of 10^3), host residency O(feed) not O(C) (the
resident gauge holds the sizes vector only while the mapped gauge
scales with C), bitwise parity mmap-vs-RAM at the common C, zero
retraces. Writes MILLION_CLIENT_AB.json (MILLION_CLIENT_AB_PATH
overrides) plus two compare-able run dirs (POPULATION_RUNS_DIR,
default artifacts/population_ab/{a,b} = smallest/largest C) that the
`population` capture step gates via `fedtorch-tpu compare --gate
tests/data/ops_runs/population_gates.json`.

Run:  python scripts/stream_bench.py
"""
from __future__ import annotations

import gc
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

from bench_timing import sync  # noqa: E402
from fedtorch_tpu.algorithms import make_algorithm  # noqa: E402
from fedtorch_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FederatedConfig, MeshConfig, ModelConfig,
    OptimConfig, TrainConfig,
)
from fedtorch_tpu.data.batching import stack_partitions  # noqa: E402
from fedtorch_tpu.data.streaming import feed_nbytes  # noqa: E402
from fedtorch_tpu.models import define_model  # noqa: E402
from fedtorch_tpu.parallel import FederatedTrainer  # noqa: E402
from fedtorch_tpu.utils.tracing import (  # noqa: E402
    RecompilationSentinel, device_memory_stats, live_buffer_summary,
)

SMOKE = os.environ.get("STREAM_BENCH_SMOKE") == "1"
# smoke: tiny MLP on MNIST-shaped synthetic rows; full: the north-star
# resnet20/cifar10-shaped workload (bench.py's config, per-round mode).
# STREAM_BENCH_ARCH overrides the full arch (a CPU-box full-population
# capture uses `mlp`; the default stays the on-chip workload).
NUM_CLIENTS = 16 if SMOKE else 100
BATCH = 8 if SMOKE else 50
K = 2 if SMOKE else 10
SPC = 64 if SMOKE else 250
ROUNDS = 3 if SMOKE else 20
ONLINE = 0.25 if SMOKE else 0.1
ARCH = "mlp" if SMOKE else os.environ.get("STREAM_BENCH_ARCH",
                                          "resnet20")
DATASET = "mnist" if (SMOKE or ARCH == "mlp") else "cifar10"
FEAT_SHAPE = (784,) if (SMOKE or ARCH == "mlp") else (32, 32, 3)
# scanned-stream window sizes (the feed x scan cell)
SCAN_WINDOWS = (1, 4) if SMOKE else (1, 4, 16)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build(plane: str):
    cfg = ExperimentConfig(
        data=DataConfig(dataset=DATASET, batch_size=BATCH,
                        data_plane=plane, augment=False),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            online_client_rate=ONLINE, algorithm="fedavg",
            sync_type="local_step"),
        model=ModelConfig(arch=ARCH, mlp_num_layers=2,
                          mlp_hidden_size=128),
        optim=OptimConfig(lr=0.1, in_momentum=not SMOKE),
        train=TrainConfig(local_step=K),
        mesh=MeshConfig(),
    ).finalize()
    rng = np.random.RandomState(0)
    feats = rng.randn(NUM_CLIENTS * SPC,
                      *FEAT_SHAPE).astype(np.float32)
    labels = rng.randint(0, 10, NUM_CLIENTS * SPC)
    parts = [np.arange(i * SPC, (i + 1) * SPC)
             for i in range(NUM_CLIENTS)]
    data = stack_partitions(feats, labels, parts)
    model = define_model(cfg, batch_size=BATCH)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def timed(tr):
    server, clients = tr.init_state(jax.random.key(0))
    server, clients, _ = tr.run_round(server, clients)
    sync(server.params)  # compile + first feed fully drained
    residency = live_buffer_summary()
    hbm = device_memory_stats()
    with RecompilationSentinel() as sentinel:
        t0 = time.perf_counter()
        for _ in range(ROUNDS):
            server, clients, _ = tr.run_round(server, clients)
        sync(server.params)
        dt = (time.perf_counter() - t0) / ROUNDS
    retraces = sum(sentinel.counts.values())
    params = jax.device_get(server.params)
    tr.invalidate_stream()
    return dt, residency, hbm, retraces, params


def main():
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform}")
    out = {
        "platform": f"{len(devs)} x {devs[0].device_kind}",
        "config": {"clients": NUM_CLIENTS, "batch": BATCH, "K": K,
                   "rows_per_client": SPC, "arch": ARCH,
                   "rounds_timed": ROUNDS, "smoke": SMOKE},
        "modes": {},
    }
    finals = {}
    for plane in ("device", "stream"):
        gc.collect()
        base_bytes = live_buffer_summary()["total_bytes"]
        tr = build(plane)
        feed_bytes = 0
        if plane == "stream":
            # one packed feed = the unit of steady-state H2D traffic
            # AND of device data residency (x the double buffer)
            kb = tr.local_steps * tr.batch_size
            feed_bytes = feed_nbytes(tr.host_store.pack(
                np.arange(tr.k_online),
                np.zeros((tr.k_online, kb), np.int64), tr.batch_size))
        dt, residency, hbm, retraces, params = timed(tr)
        store_mb = tr.host_store.nbytes / 2**20 if plane == "stream" \
            else sum(np.asarray(leaf).nbytes for leaf in
                     jax.tree.leaves(tr.data.x)) / 2**20
        out["modes"][plane] = {
            "ms_per_round": round(dt * 1e3, 2),
            "h2d_mb_per_round": round(feed_bytes / 2**20, 3)
            if plane == "stream" else 0.0,
            "client_store_mb": round(store_mb, 2),
            "live_device_bytes_after_warmup": max(
                residency["total_bytes"] - base_bytes, 0),
            "retraces_during_timed_rounds": retraces,
        }
        if hbm:
            peak = max(v.get("peak_bytes_in_use") or 0
                       for v in hbm.values())
            out["modes"][plane]["peak_hbm_bytes"] = int(peak)
        finals[plane] = params
        log(f"{plane:6s}: {dt*1e3:8.2f} ms/round, "
            f"{residency['total_bytes']/2**20:7.1f} MB live on device, "
            f"{retraces} retraces")
        del tr
    # -- scanned-stream arm (the builder's feed x scan cell) -----------
    scan_rows = {}
    feed_mb = out["modes"]["stream"]["h2d_mb_per_round"]
    for R in SCAN_WINDOWS:
        gc.collect()
        tr = build("stream")
        calls = max(1, ROUNDS // R)
        server, clients = tr.init_state(jax.random.key(0))
        server, clients, _ = tr.run_rounds(server, clients, R)
        sync(server.params)  # compile + first window drained
        with RecompilationSentinel() as sentinel:
            t0 = time.perf_counter()
            for _ in range(calls):
                server, clients, _ = tr.run_rounds(server, clients, R)
            sync(server.params)
            dt = (time.perf_counter() - t0) / (calls * R)
        retraces = sum(sentinel.counts.values())
        params = jax.device_get(server.params)
        tr.invalidate_stream()
        del tr
        gc.collect()
        # the device reference scans the SAME round sequence — the
        # parity bar is bitwise against the resident scan program
        tr = build("device")
        server, clients = tr.init_state(jax.random.key(0))
        for _ in range(calls + 1):
            server, clients, _ = tr.run_rounds(server, clients, R)
        ref = jax.device_get(server.params)
        del tr
        # ref/params hold host numpy (device_get above)
        max_diff = max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(ref)))
        scan_rows[f"R={R}"] = {
            "ms_per_round": round(dt * 1e3, 2),
            "rounds_timed": calls * R,
            "retraces_during_timed_rounds": retraces,
            "window_h2d_mb": round(feed_mb * R, 3),
            "parity_bitwise_vs_device_scan": max_diff == 0.0,
            "parity_max_abs_diff": max_diff,
        }
        log(f"stream+scan R={R:3d}: {dt*1e3:8.2f} ms/round, "
            f"{retraces} retraces, max|Δ| vs device scan {max_diff}")
    d, s = (out["modes"]["device"]["ms_per_round"],
            out["modes"]["stream"]["ms_per_round"])
    best_R = min(scan_rows, key=lambda k: scan_rows[k]["ms_per_round"])
    best = scan_rows[best_R]["ms_per_round"]
    out["scanned_stream"] = {
        "windows": scan_rows,
        "best_window": best_R,
        "best_ms_per_round": best,
        # scan must beat the per-round stream dispatch...
        "stream_scan_over_stream": round(best / s, 3),
        # ...and this is the stream-vs-device gap the lever closes
        "stream_scan_over_device_walltime": round(best / d, 3),
        "gap_closed_to_leq_1x": bool(best <= d),
    }
    out["stream_over_device_walltime"] = round(s / d, 3)
    out["overlap_within_10pct"] = bool(s <= 1.10 * d)
    # finals hold HOST numpy (device_get in timed()) — no device sync
    diffs = [float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
             for a, b in zip(jax.tree.leaves(finals["device"]),
                             jax.tree.leaves(finals["stream"]))]
    max_diff = max(diffs)  # plain Python floats from the line above
    out["parity_bitwise"] = max_diff == 0.0
    out["parity_max_abs_diff"] = max_diff
    out["residency_ratio_stream_over_device"] = round(
        out["modes"]["stream"]["live_device_bytes_after_warmup"]
        / max(out["modes"]["device"]["live_device_bytes_after_warmup"],
              1), 4)
    path = os.environ.get("STREAM_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "STREAM_AB.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


# -- population-scaling arm (STREAM_BENCH_POPULATION=1) ------------------
POP_SIZES = (200, 1_000) if SMOKE else (1_000, 100_000, 1_000_000)
POP_K = 4 if SMOKE else 8          # FIXED cohort: the independent var
#                                    is C, never the per-round work
POP_NMAX = 16
POP_DIM = 16
POP_BATCH = 8 if SMOKE else 32
POP_LOCAL = 2 if SMOKE else 40
POP_ROUNDS = 3 if SMOKE else 12    # timed rounds after the warmup
POP_SETTLE = 1 if SMOKE else 6     # untimed settling rounds: right
#                                    after a ~1 GB store write the
#                                    first rounds pay the kernel's
#                                    dirty-page writeback + allocator
#                                    growth on this core — warm past it


def _pop_write_store(store_dir: str, C: int, seed: int = 1234):
    """Materialize the synthetic population chunk-wise — RAM stays
    O(chunk) however large C gets."""
    from fedtorch_tpu.data.streaming import MmapStoreWriter
    rng = np.random.RandomState(seed)
    writer = MmapStoreWriter(
        store_dir, n_max=POP_NMAX, x_feat=(POP_DIM,), y_feat=(),
        x_dtype=np.float32, y_dtype=np.int32)
    chunk = 65536
    for lo in range(0, C, chunk):
        n = min(chunk, C - lo)
        x = rng.randn(n, POP_NMAX, POP_DIM).astype(np.float32)
        y = rng.randint(0, 10, (n, POP_NMAX)).astype(np.int32)
        sizes = rng.randint(1, POP_NMAX + 1, n).astype(np.int32)
        writer.append(x, y, sizes)
    return writer.finalize()


def _pop_ram_data(C: int, seed: int = 1234):
    """The SAME population as `_pop_write_store(C, seed)`, held in RAM
    (identical RandomState stream) — the parity twin."""
    from fedtorch_tpu.data.batching import ClientData
    rng = np.random.RandomState(seed)
    xs, ys, ss = [], [], []
    chunk = 65536
    for lo in range(0, C, chunk):
        n = min(chunk, C - lo)
        xs.append(rng.randn(n, POP_NMAX, POP_DIM).astype(np.float32))
        ys.append(rng.randint(0, 10, (n, POP_NMAX)).astype(np.int32))
        ss.append(rng.randint(1, POP_NMAX + 1, n).astype(np.int32))
    return ClientData(x=np.concatenate(xs), y=np.concatenate(ys),
                      sizes=np.concatenate(ss))


def _pop_cfg(C: int, store: str, store_dir: str = ""):
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=POP_DIM,
                        batch_size=POP_BATCH, data_plane="stream",
                        store=store, store_dir=store_dir,
                        augment=False),
        federated=FederatedConfig(
            federated=True, num_clients=C,
            # rate chosen so max(int(rate*C), 1) == POP_K exactly
            online_client_rate=(POP_K + 0.5) / C,
            algorithm="fedavg", sync_type="local_step",
            participation_mode="sparse"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1),
        train=TrainConfig(local_step=POP_LOCAL),
        mesh=MeshConfig(),
    ).finalize()


def _pop_run(tr):
    """Warmup (compile + first feed) then per-round timed steady
    rounds under the recompilation sentinel. Returns (per-round rows,
    retraces, gauges, final server params, final client state)."""
    server, clients = tr.init_state(jax.random.key(0))
    # warmup: round trace + compile, then the scalar-fetch programs
    # (shape-specialized to this C — their first call compiles), then
    # the settling rounds, so the timed window starts from the steady
    # allocator / page-cache state
    server, clients, m = tr.run_round(server, clients)
    sync(server.params)
    jax.device_get(tr.round_scalars_dev(clients, m))
    for _ in range(POP_SETTLE):
        server, clients, m = tr.run_round(server, clients)
        jax.device_get(tr.round_scalars_dev(clients, m))
    rows = []
    with RecompilationSentinel() as sentinel:
        for r in range(POP_ROUNDS):
            t0 = time.perf_counter()
            server, clients, m = tr.run_round(server, clients)
            sync(server.params)
            dt = time.perf_counter() - t0
            # the CLI loop's one batched scalar fetch — never the [C]
            # metrics leaves
            sc = jax.device_get(tr.round_scalars_dev(clients, m))
            n = max(float(sc["n_online"]), 1.0)
            rows.append({"round": r, "round_s": dt,
                         "loss": float(sc["loss_sum"]) / n,
                         "acc": float(sc["acc_sum"]) / n,
                         "comm_bytes": float(sc["comm_bytes"])})
    retraces = sum(sentinel.counts.values())
    gauges = tr.telemetry_gauges()
    params = jax.device_get(server.params)
    cstate = jax.device_get(clients)
    tr.invalidate_stream()
    return rows, retraces, gauges, params, cstate


def _pop_write_run_dir(path: str, rows, meta: dict, gauges: dict):
    os.makedirs(path, exist_ok=True)
    keep = {k: v for k, v in gauges.items()
            if k.startswith("stream_store_")}
    with open(os.path.join(path, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({"schema": "fedtorch_tpu.metrics/v1",
                            "created_unix": time.time(),
                            "run": meta}) + "\n")
        for row in rows:
            f.write(json.dumps(dict(row, **keep)) + "\n")


def population_main():
    import shutil
    import tempfile
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform} (population arm)")
    runs_dir = os.environ.get("POPULATION_RUNS_DIR") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "artifacts", "population_ab")
    out = {
        "platform": f"{len(devs)} x {devs[0].device_kind}",
        "config": {"populations": list(POP_SIZES), "k_online": POP_K,
                   "n_max": POP_NMAX, "dim": POP_DIM,
                   "batch": POP_BATCH, "K": POP_LOCAL,
                   "rounds_timed": POP_ROUNDS, "smoke": SMOKE,
                   "store": "mmap",
                   "participation_mode": "sparse"},
        "populations": {},
    }
    steady = {}
    for i, C in enumerate(POP_SIZES):
        gc.collect()
        store_dir = tempfile.mkdtemp(prefix=f"popstore_{C}_")
        t0 = time.perf_counter()
        _pop_write_store(store_dir, C)
        build_s = time.perf_counter() - t0
        from fedtorch_tpu.data.streaming import MmapClientStore
        stub = MmapClientStore(store_dir).as_client_data()
        cfg = _pop_cfg(C, "mmap", store_dir)
        tr = FederatedTrainer(cfg, define_model(cfg, POP_BATCH),
                              make_algorithm(cfg), stub)
        assert tr.k_online == POP_K, tr.k_online
        rows, retraces, gauges, params, cstate = _pop_run(tr)
        del tr
        # steady mean excludes the first timed round, mirroring
        # report.summarize's round_s_mean_steady on the run dirs
        steady[C] = float(np.mean([r["round_s"] for r in rows[1:]]))
        row = {
            "clients": C,
            "store_build_s": round(build_s, 2),
            "ms_per_round_steady": round(steady[C] * 1e3, 2),
            "retraces_during_timed_rounds": retraces,
            "store_resident_mb": round(
                gauges.get("stream_store_resident_mb", 0.0), 3),
            "store_mapped_mb": round(
                gauges.get("stream_store_mapped_mb", 0.0), 3),
        }
        if C == POP_SIZES[0]:
            # parity twin: the SAME population in the RAM store — the
            # trajectory (server params AND client state) must be
            # bitwise-identical; only the byte source differs
            cfg_ram = _pop_cfg(C, "ram")
            tr2 = FederatedTrainer(cfg_ram,
                                   define_model(cfg_ram, POP_BATCH),
                                   make_algorithm(cfg_ram),
                                   _pop_ram_data(C))
            _, _, _, params2, cstate2 = _pop_run(tr2)
            del tr2
            diffs = [float(np.max(np.abs(np.asarray(a)
                                         - np.asarray(b))))
                     if np.asarray(a).size else 0.0
                     for a, b in zip(jax.tree.leaves((params, cstate)),
                                     jax.tree.leaves((params2,
                                                      cstate2)))]
            row["parity_bitwise_mmap_vs_ram"] = max(diffs) == 0.0
            row["parity_max_abs_diff"] = max(diffs)
            out["parity_bitwise_mmap_vs_ram"] = max(diffs) == 0.0
        meta = {"bench": "population", "clients": C, "store": "mmap",
                "participation_mode": "sparse", "k_online": POP_K}
        if i == 0:
            _pop_write_run_dir(os.path.join(runs_dir, "a"), rows,
                               meta, gauges)
        if i == len(POP_SIZES) - 1:
            _pop_write_run_dir(os.path.join(runs_dir, "b"), rows,
                               meta, gauges)
        out["populations"][f"C={C}"] = row
        log(f"C={C:>9,d}: {steady[C]*1e3:8.2f} ms/round steady, "
            f"store build {build_s:6.1f}s, resident "
            f"{row['store_resident_mb']:.3f} MB, mapped "
            f"{row['store_mapped_mb']:.1f} MB, {retraces} retraces")
        shutil.rmtree(store_dir, ignore_errors=True)
    lo, hi = POP_SIZES[0], POP_SIZES[-1]
    out["round_wall_ratio_max_over_min_pop"] = round(
        steady[hi] / steady[lo], 3)
    out["round_wall_flat_within_10pct"] = bool(
        steady[hi] <= 1.10 * steady[lo])
    big = out["populations"][f"C={hi}"]
    out["residency_mapped_not_resident"] = bool(
        big["store_resident_mb"] < 0.05 * big["store_mapped_mb"])
    out["zero_retraces"] = all(
        r["retraces_during_timed_rounds"] == 0
        for r in out["populations"].values())
    out["runs_dir"] = runs_dir
    path = os.environ.get("MILLION_CLIENT_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "MILLION_CLIENT_AB.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if os.environ.get("STREAM_BENCH_POPULATION") == "1":
        population_main()
    else:
        main()
