"""Async-commit-plane A/B: `sync_mode='sync'` vs `'async'` under the
straggler-heavy chaos schedule (scripts/chaos_suite.py's preset).

The claim under test (ISSUE 6): a synchronous round is gated on its
SLOWEST online client — under a long-tail delay distribution the round
clock is the tail — while the FedBuff-style buffer commits on the
FASTEST m arrivals, so commit cadence is tail-independent. Both planes
share one deterministic delay model (threefry draws off the experiment
key, async_plane/scheduler.py), so the A/B compares:

* **virtual commit cadence** — the event clock: per sync round, the
  MAX of its k dispatch delays (`simulate_sync_round_times`); per
  async commit, `AsyncSchedule.commit_times` deltas. The headline is
  aggregated client updates per virtual time unit, which normalizes
  for the buffer committing m <= k updates at a time;
* **wall-clock per commit** (fetch-synced, bench_timing.sync) — the
  device cost of the commit program vs the round program;
* **accuracy parity** at an equal client-update budget (R sync rounds
  of k updates == R*k/m async commits of m), against the chaos-suite
  <=5-point bar;
* **trace-once** — the commit program must not retrace mid-run
  (RecompilationSentinel), plus the scheduler's straggler/ring-clamp
  counters.

A third ``async_trace`` leg reruns the async side under the
deployment-realism availability model (robustness/availability.py:
device-class delays + diurnal dropouts) at the same commit budget —
the default sync/async legs keep the legacy delay chain bitwise.

Writes ASYNC_AB.json (ASYNC_AB_PATH overrides, for the test smoke).
ASYNC_BENCH_SMOKE=1 shrinks the workload for CPU CI.

Run:  python scripts/async_bench.py
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

from bench_timing import sync  # noqa: E402
from chaos_suite import straggler_heavy_fault  # noqa: E402
from fedtorch_tpu.algorithms import make_algorithm  # noqa: E402
from fedtorch_tpu.async_plane import AsyncFederatedTrainer  # noqa: E402
from fedtorch_tpu.async_plane.scheduler import (  # noqa: E402
    simulate_sync_round_times,
)
from fedtorch_tpu.config import (  # noqa: E402
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data  # noqa: E402
from fedtorch_tpu.models import define_model  # noqa: E402
from fedtorch_tpu.parallel import FederatedTrainer, evaluate  # noqa: E402
from fedtorch_tpu.utils.tracing import RecompilationSentinel  # noqa: E402

SMOKE = os.environ.get("ASYNC_BENCH_SMOKE") == "1"
NUM_CLIENTS = 12 if SMOKE else 100
BATCH = 8 if SMOKE else 50
K = 2 if SMOKE else 10
SYNC_ROUNDS = 4 if SMOKE else 40
ONLINE = 0.5 if SMOKE else 0.1
ARCH = "logistic_regression" if SMOKE else "mlp"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build(sync_mode: str, num_comms: int, fault_extra: dict = None):
    fault_kwargs = dict(straggler_heavy_fault(), **(fault_extra or {}))
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=30,
                        batch_size=BATCH, synthetic_alpha=0.5,
                        synthetic_beta=0.5),
        federated=FederatedConfig(
            federated=True, num_clients=NUM_CLIENTS,
            num_comms=num_comms, online_client_rate=ONLINE,
            algorithm="fedavg", sync_type="local_step",
            sync_mode=sync_mode),
        model=ModelConfig(arch=ARCH, mlp_num_layers=2,
                          mlp_hidden_size=64),
        optim=OptimConfig(lr=0.5, weight_decay=0.0),
        train=TrainConfig(local_step=K),
        fault=FaultConfig(**fault_kwargs),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=BATCH)
    cls = AsyncFederatedTrainer if sync_mode == "async" \
        else FederatedTrainer
    tr = cls(cfg, model, make_algorithm(cfg), data.train)
    return cfg, tr, data


def timed(tr, steps: int):
    """Warmup one step (the expected trace), then time the rest under
    the sentinel."""
    server, clients = tr.init_state(jax.random.key(0))
    server, clients, _ = tr.run_round(server, clients)
    sync(server.params)
    with RecompilationSentinel() as sentinel:
        t0 = time.perf_counter()
        stale_dev = []
        for _ in range(steps - 1):
            server, clients, m = tr.run_round(server, clients)
            # defer the fetch: a per-commit float() would serialize a
            # blocking transfer into the timed window (lint FTL001)
            stale_dev.append(m.staleness_mean)
        sync(server.params)
        dt = (time.perf_counter() - t0) / max(steps - 1, 1)
    retraces = sum(sentinel.counts.values())
    stale = [float(x) for x in jax.device_get(stale_dev)]
    return server, dt, retraces, sum(stale) / max(len(stale), 1)


def main():
    devs = jax.devices()
    log(f"devices: {len(devs)} x {devs[0].platform}")
    k = max(int(ONLINE * NUM_CLIENTS), 1)
    m = max(k // 2, 1)  # the auto buffer size
    commits = SYNC_ROUNDS * k // m  # equal client-update budget
    out = {
        "platform": f"{len(devs)} x {devs[0].device_kind}",
        "config": {"clients": NUM_CLIENTS, "k_online": k,
                   "buffer_m": m, "batch": BATCH, "K": K, "arch": ARCH,
                   "sync_rounds": SYNC_ROUNDS, "async_commits": commits,
                   "fault": straggler_heavy_fault(), "smoke": SMOKE},
        "modes": {},
    }

    # -- sync leg --------------------------------------------------------
    cfg, tr, data = build("sync", SYNC_ROUNDS)
    server, dt, retraces, _ = timed(tr, SYNC_ROUNDS)
    acc = float(evaluate(tr.model, server.params, data.test_x,
                         data.test_y).top1)
    # the SAME key the async leg's scheduler draws its delays from:
    # server.rng (init_state's split of key(0), never advanced by the
    # round program) — so the two legs share one delay model and the
    # comparison is PAIRED per dispatch id, not two unrelated streams
    key_data = np.asarray(
        jax.device_get(jax.random.key_data(server.rng)))
    key_impl = jax.random.key_impl(server.rng)
    flt = straggler_heavy_fault()
    round_times = simulate_sync_round_times(
        key_data, key_impl, rounds=SYNC_ROUNDS, k_online=k,
        straggler_rate=flt["straggler_rate"],
        straggler_step_frac=flt["straggler_step_frac"])
    vtotal = float(np.sum(round_times))
    out["modes"]["sync"] = {
        "top1": round(acc, 4),
        "ms_per_commit_wall": round(dt * 1e3, 2),
        "retraces_during_timed": retraces,
        "virtual_time_total": round(vtotal, 3),
        "virtual_mean_step_interval": round(vtotal / SYNC_ROUNDS, 3),
        "commits_per_virtual_unit": round(SYNC_ROUNDS / vtotal, 4),
        "client_updates_per_virtual_unit": round(
            SYNC_ROUNDS * k / vtotal, 4),
    }
    log(f"sync : top1 {acc:.4f}  {dt*1e3:.1f} ms/round  "
        f"virtual {vtotal/SYNC_ROUNDS:.2f}/round (max of {k} delays)")

    # -- async leg -------------------------------------------------------
    cfg, tr, data = build("async", commits)
    server, dt_a, retraces_a, stale = timed(tr, commits)
    acc_a = float(evaluate(tr.model, server.params, data.test_x,
                           data.test_y).top1)
    ct = np.asarray(tr._sched.commit_times)
    stats = tr.schedule_stats
    vtotal_a = float(ct[-1])
    out["modes"]["async"] = {
        "top1": round(acc_a, 4),
        "ms_per_commit_wall": round(dt_a * 1e3, 2),
        "retraces_during_timed": retraces_a,
        "virtual_time_total": round(vtotal_a, 3),
        "virtual_mean_step_interval": round(vtotal_a / commits, 3),
        "commits_per_virtual_unit": round(commits / vtotal_a, 4),
        "client_updates_per_virtual_unit": round(
            commits * m / vtotal_a, 4),
        "staleness_mean": round(stale, 3),
        "scheduler": {"dispatches": stats.dispatches,
                      "stragglers": stats.stragglers,
                      "ring_clamped": stats.staleness_clamped,
                      "dropouts": stats.dropouts},
    }
    tr.invalidate_stream()
    log(f"async: top1 {acc_a:.4f}  {dt_a*1e3:.1f} ms/commit  "
        f"virtual {vtotal_a/commits:.2f}/commit  "
        f"staleness {stale:.2f}")

    # -- async leg, trace availability model -----------------------------
    # same commit budget, but arrivals drawn from the deployment-realism
    # trace (robustness/availability.py): device-class delay multipliers
    # + diurnal mid-round dropouts. The default legs above are untouched
    # (their delay model is the legacy chain, bitwise), so this leg
    # measures what deployment realism costs the commit cadence.
    cfg, tr, data = build("async", commits,
                          fault_extra={"avail_model": "trace",
                                       "avail_dropout_rate": 0.1,
                                       "avail_diurnal_period": 8})
    server, dt_t, retraces_t, stale_t = timed(tr, commits)
    acc_t = float(evaluate(tr.model, server.params, data.test_x,
                           data.test_y).top1)
    ct_t = np.asarray(tr._sched.commit_times)
    stats_t = tr.schedule_stats
    vtotal_t = float(ct_t[-1])
    out["modes"]["async_trace"] = {
        "top1": round(acc_t, 4),
        "ms_per_commit_wall": round(dt_t * 1e3, 2),
        "retraces_during_timed": retraces_t,
        "virtual_time_total": round(vtotal_t, 3),
        "virtual_mean_step_interval": round(vtotal_t / commits, 3),
        "commits_per_virtual_unit": round(commits / vtotal_t, 4),
        "client_updates_per_virtual_unit": round(
            commits * m / vtotal_t, 4),
        "staleness_mean": round(stale_t, 3),
        "scheduler": {"dispatches": stats_t.dispatches,
                      "stragglers": stats_t.stragglers,
                      "ring_clamped": stats_t.staleness_clamped,
                      "dropouts": stats_t.dropouts},
    }
    tr.invalidate_stream()
    log(f"async_trace: top1 {acc_t:.4f}  {dt_t*1e3:.1f} ms/commit  "
        f"virtual {vtotal_t/commits:.2f}/commit  "
        f"dropouts {stats_t.dropouts}")

    # -- the verdict -----------------------------------------------------
    s, a = out["modes"]["sync"], out["modes"]["async"]
    out["commit_rate_speedup_virtual"] = round(
        a["commits_per_virtual_unit"] / s["commits_per_virtual_unit"], 3)
    out["update_rate_speedup_virtual"] = round(
        a["client_updates_per_virtual_unit"]
        / s["client_updates_per_virtual_unit"], 3)
    out["accuracy_gap_points"] = round((acc - acc_a) * 100.0, 2)
    # the bar: async commits are NOT gated on the slowest client — its
    # mean commit interval beats the sync round's straggler-set clock
    out["async_not_tail_gated"] = bool(
        a["virtual_mean_step_interval"] < s["virtual_mean_step_interval"])
    log(f"virtual commit-rate speedup {out['commit_rate_speedup_virtual']}x"
        f", update-rate {out['update_rate_speedup_virtual']}x, "
        f"acc gap {out['accuracy_gap_points']:+.2f}pts")

    path = os.environ.get("ASYNC_AB_PATH") or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ASYNC_AB.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
