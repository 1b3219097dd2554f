"""Telemetry overhead A/B -> TELEMETRY_AB.json (docs/observability.md).

Measures what turning ``--telemetry`` on costs a training run: the
SAME round loop the CLI drives (jitted round + the one batched scalar
fetch + the per-round telemetry emissions), A/B'd across
``off`` / ``default`` / ``costs`` / ``cohort_off`` / ``cohort`` /
``debug`` arms on one workload, same seed, best-of-``reps`` wall per
arm. The ``costs`` arm is ``default`` plus the device-side gauges
(measured MFU + the HBM watermark pair from a pre-captured
program_costs — ISSUE 8). The ``cohort`` arm (ISSUE 14) is
``default`` plus the federation-plane observability: the per-client
cohort vectors riding the batched fetch, the ledger fold, and the
cohort row gauges — measured against ``cohort_off`` (the SAME
cohort-stats program under default telemetry, no federation-plane
emission), because ``--cohort_stats`` changes the traced program and
default telemetry holds its own bar via the ``default`` arm; the
combined program+default delta vs bare off is reported separately
(``baseline_frac_vs_off``).
Acceptance bar: ``default`` AND ``costs`` AND ``cohort`` each add
<= 1% to steady-state round wall-time against their baselines
(ISSUE 7/8/14 hard bar) — telemetry that taxes the round clock would
be measuring its own overhead. The ``ledger_memory`` row additionally
proves the ledger's O(min(C, budget)) bound with a synthetic C=10^6
population.

Also records unit costs (ns/span, us/metrics-row, us/health-replace)
so a regression is attributable to a specific emitter.

Presets:
  northstar  ResNet-20, 32x32 class-conditional synthetic, B=50, K=10
             (the certified north-star shape — the on-chip arm
             scripts/tpu_capture.sh 'telemetry' runs)
  host       wide MLP on synthetic rows (CPU-friendly rounds in the
             tens of ms — the committed-artifact arm; a tiny round
             would put the 1% bar at single-digit us and measure
             filesystem noise instead of telemetry)
  smoke      seconds-fast shapes for the slow-lane pytest

Usage:
    python scripts/telemetry_bench.py [--preset auto] [--rounds N]
        [--reps R] [--capture-run DIR]

``--capture-run DIR`` additionally drives one FULL ``run_experiment``
(telemetry default) on the preset's config with ``--run_dir DIR`` so
the run dir's metrics.jsonl + trace.json land as capture artifacts.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OUT = os.path.join(REPO, "TELEMETRY_AB.json")
ACCEPT_OVERHEAD = 0.01  # the <= 1% bar, default verbosity


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def build_workload(preset: str):
    import numpy as np

    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FederatedConfig, ModelConfig,
        OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions

    rng = np.random.RandomState(7)
    if preset == "northstar":
        C, B, K, n_per = 100, 50, 10, 200
        class_means = rng.randn(10, 32, 32, 3).astype(np.float32) * 0.8
        labels = rng.randint(0, 10, C * n_per)
        feats = class_means[labels] + rng.randn(
            C * n_per, 32, 32, 3).astype(np.float32)
        arch, dataset = "resnet20", "cifar10"
        rate = 0.1
    else:
        # host: rounds in the tens of ms on one CPU core; smoke:
        # seconds-fast for the slow-lane pytest
        C, B, K, n_per = (20, 50, 10, 200) if preset == "host" \
            else (6, 8, 2, 24)
        hidden = 800 if preset == "host" else 32
        dim = 256 if preset == "host" else 16
        labels = rng.randint(0, 10, C * n_per)
        feats = rng.randn(C * n_per, dim).astype(np.float32) \
            + labels[:, None] * 0.05
        arch, dataset = "mlp", "synthetic"
        rate = 0.25 if preset == "host" else 0.5
    parts = [np.arange(i * n_per, (i + 1) * n_per) for i in range(C)]
    data = stack_partitions(feats, labels, parts)
    cfg = ExperimentConfig(
        data=DataConfig(dataset=dataset, batch_size=B,
                        synthetic_dim=feats.shape[-1]),
        federated=FederatedConfig(
            federated=True, num_clients=C, online_client_rate=rate,
            algorithm="fedavg", sync_type="local_step"),
        model=ModelConfig(
            arch=arch,
            **({"mlp_hidden_size": hidden} if arch == "mlp" else {})),
        optim=OptimConfig(lr=0.1, in_momentum=True),
        train=TrainConfig(local_step=K),
    ).finalize()
    return cfg, data


def make_trainer(cfg, data):
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data)


def timed_loop(trainer, rounds: int, tel, run_dir,
               cost_cap=None, ledger=None) -> float:
    """The CLI loop's telemetry-relevant body, per-arm: jitted round,
    ONE batched scalar fetch, row/health emission (plus, on the costs
    arm, the per-round device gauges — measured MFU + the HBM
    watermark pair; on the cohort arm, the per-client cohort vectors
    riding the same fetch + the ledger fold + the cohort gauges).
    Returns seconds for the whole loop, fetch-synced (the per-round
    scalar fetch already materializes host bytes every round — the
    queued-in-order concern does not apply)."""
    import jax

    from fedtorch_tpu.telemetry.critical_path import (
        StreamOverlapTracker,
    )

    server, clients = trainer.init_state(jax.random.key(6))
    # the ops-plane derivation the CLI loop now runs per round
    # (ISSUE 15): a no-op on the device plane, the overlap gauge on
    # the stream plane — included so every arm pays what the loop pays
    overlap = StreamOverlapTracker()
    t0 = time.perf_counter()
    for r in range(rounds):
        rd0 = time.perf_counter()
        with tel.span("round", round=r):
            server, clients, metrics = trainer.run_round(server, clients)
        rt0 = time.perf_counter()
        led = None
        with tel.span("scalar_fetch", round=r):
            if ledger is None:
                sc = trainer.round_host_scalars(clients, metrics)
            else:
                sc_dev, led = jax.device_get(
                    (trainer.round_scalars_dev(clients, metrics),
                     trainer.cohort_fetch_dev(metrics)))
                sc = {k: float(v) for k, v in sc_dev.items()}
        rt1 = time.perf_counter()
        # attribution matches the CLI loop's semantics: round_s is the
        # dispatch-to-completion wall (here the fetch is what blocks
        # on the round, so it closes the round's clock), fetch_s the
        # transfer leg alone — the two must not double-count or a
        # report over the captured run dir prints a bogus breakdown
        fetch_s = rt1 - rt0
        n = max(sc["n_online"], 1.0)
        row = {"round": r, "round_s": rt1 - rd0,
               "loss": sc["loss_sum"] / n,
               "acc": sc["acc_sum"] / n, "lr": sc["lr"],
               "n_online": sc["n_online"],
               "comm_bytes": sc["comm_bytes"],
               "mean_epoch": sc["mean_epoch"], "fetch_s": fetch_s,
               "dropped": sc["dropped"], "stragglers": sc["stragglers"],
               "rejected": sc["rejected"], "clipped": sc["clipped"],
               "staleness": sc["staleness"]}
        if led is not None:
            row["cohort_dispersion"] = sc["cohort_dispersion"]
            nq = led["norm_q"]
            row.update({
                "cohort_norm_min": float(nq[0]),
                "cohort_norm_q25": float(nq[1]),
                "cohort_norm_med": float(nq[2]),
                "cohort_norm_q75": float(nq[3]),
                "cohort_norm_max": float(nq[4])})
            ledger.update(r, led)
            row.update(ledger.stats())
        row.update(trainer.telemetry_gauges())
        eff = overlap.observe(row)
        if eff is not None:
            row["overlap_efficiency"] = eff
        if cost_cap is not None:
            row.update(cost_cap.round_gauges(rt1 - rd0))
        tel.round_row(row)
        tel.health_update("running", round_idx=r + 1,
                          staleness=sc["staleness"])
    return time.perf_counter() - t0


def unit_costs() -> dict:
    """Microbench the emitters in isolation (committed alongside the
    A/B so a future regression names its culprit)."""
    import tempfile

    from fedtorch_tpu.telemetry import Telemetry

    d = tempfile.mkdtemp(prefix="telemetry_unit_")
    tel = Telemetry(d, level="default")
    n = 5000
    t0 = time.perf_counter()
    for i in range(n):
        with tel.span("unit"):
            pass
    span_ns = (time.perf_counter() - t0) / n * 1e9
    row = {"round": 0, "round_s": 0.1, "loss": 1.0, "acc": 0.5,
           "lr": 0.1, "n_online": 5.0, "comm_bytes": 1e6}
    t0 = time.perf_counter()
    for i in range(1000):
        tel.round_row(dict(row, round=i))
    row_us = (time.perf_counter() - t0) / 1000 * 1e6
    t0 = time.perf_counter()
    for i in range(1000):
        tel.health_update("running", round_idx=i)
    health_us = (time.perf_counter() - t0) / 1000 * 1e6
    tel.close()
    # the ledger fold in isolation (dense mode, k=10 online / round):
    # the recurring host cost of the cohort arm minus the fetch — the
    # deterministic evidence when the A/B arms are noise-bound
    import numpy as np

    from fedtorch_tpu.telemetry.ledger import ClientLedger
    led = ClientLedger(tempfile.mkdtemp(prefix="ledger_unit_"),
                       num_clients=100, flush_every=10 ** 9)
    rng = np.random.RandomState(0)
    rounds_vec = [
        {"idx": rng.choice(100, size=10, replace=False),
         "online": np.ones(10), "accept": np.ones(10),
         "selected": np.ones(10), "suspicion": rng.rand(10),
         "staleness": np.zeros(10), "norm_q": np.zeros(5)}
        for _ in range(64)]
    t0 = time.perf_counter()
    for i in range(1000):
        led.update(i, rounds_vec[i % 64])
    ledger_us = (time.perf_counter() - t0) / 1000 * 1e6
    # the ops-plane gauge arm (ISSUE 15), paired per-leg like the
    # cohort verdict: the per-round overlap derivation on a stream-
    # gauge row, and the device-gauge surplus of the two critical-path
    # fields (round_gauges with a captured primary vs the same row
    # maths without them is two float ops — measure the whole gauge
    # call so the number is the honest recurring cost)
    from fedtorch_tpu.telemetry.critical_path import (
        StreamOverlapTracker,
    )
    trk = StreamOverlapTracker()
    srow = {"stream_gather_s": 0.0, "stream_h2d_s": 0.0,
            "stream_wait_s": 0.0}
    t0 = time.perf_counter()
    for i in range(5000):
        srow["stream_gather_s"] = i * 1e-3
        srow["stream_h2d_s"] = i * 5e-4
        srow["stream_wait_s"] = i * 1e-4
        trk.observe(srow)
    overlap_us = (time.perf_counter() - t0) / 5000 * 1e6
    return {"span_ns": round(span_ns, 1),
            "metrics_row_us": round(row_us, 2),
            "health_replace_us": round(health_us, 2),
            "ledger_fold_us": round(ledger_us, 2),
            "overlap_derive_us": round(overlap_us, 3)}


def cohort_fetch_delta_us(trainer_cohort, iters: int = 200) -> float:
    """PAIRED microbench of the one transfer the cohort arm changes:
    ``device_get((scalars, cohort_vectors))`` vs
    ``device_get(scalars)`` on the same materialized round outputs,
    alternated back-to-back so load drift cancels. A 1-core box's
    whole-round A/B has a multi-percent noise envelope; this paired
    per-leg delta resolves the actual microseconds."""
    import jax

    server, clients = trainer_cohort.init_state(jax.random.key(6))
    server, clients, metrics = trainer_cohort.run_round(server, clients)
    jax.block_until_ready(server.params)
    plain = both = 0.0
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.device_get(trainer_cohort.round_scalars_dev(clients,
                                                        metrics))
        t1 = time.perf_counter()
        jax.device_get((trainer_cohort.round_scalars_dev(clients,
                                                         metrics),
                        trainer_cohort.cohort_fetch_dev(metrics)))
        t2 = time.perf_counter()
        plain += t1 - t0
        both += t2 - t1
    return max(both - plain, 0.0) / iters * 1e6


def ledger_memory(budget: int = 65536, k: int = 64,
                  rounds: int = 50) -> dict:
    """The ledger memory-bound measurement (ISSUE 14 acceptance):
    feed synthetic cohort rows to a dense ledger at a small C and a
    sketch ledger at C=10^6 with the same budget, and record the
    measured footprint — O(min(C, budget)), NOT O(C): the 10^6-client
    sketch must undercut what dense counters would cost at the budget
    population, by orders of magnitude vs dense-at-C."""
    import tempfile

    import numpy as np

    from fedtorch_tpu.telemetry.ledger import (
        LEDGER_COUNTERS, ClientLedger,
    )

    rng = np.random.RandomState(0)
    out = {"budget": budget, "clients_per_round": k, "rounds": rounds}
    dense_at_c = None
    for name, C in (("dense_c4096", 4096), ("sketch_c1e6", 1_000_000)):
        led = ClientLedger(tempfile.mkdtemp(prefix="ledger_mem_"),
                           num_clients=C, sketch_budget=budget,
                           flush_every=10 ** 9)
        t0 = time.perf_counter()
        for r in range(rounds):
            idx = rng.choice(C, size=k, replace=False)
            led.update(r, {
                "idx": idx, "online": np.ones(k), "accept": np.ones(k),
                "selected": np.ones(k), "suspicion": rng.rand(k),
                "staleness": np.zeros(k), "norm_q": np.zeros(5)})
        per_round_us = (time.perf_counter() - t0) / rounds * 1e6
        out[name] = {"clients": C, "mode": led.mode,
                     "bytes": led.memory_bytes(),
                     "tracked": led.tracked(),
                     "update_us_per_round": round(per_round_us, 1)}
        if name == "sketch_c1e6":
            dense_at_c = C * 8 * len(LEDGER_COUNTERS)
    # the bound: the 10^6-client sketch costs O(budget) bytes, not the
    # 56 MB dense counters at C=10^6 would
    out["dense_bytes_at_c1e6"] = dense_at_c
    out["bounded"] = bool(
        out["sketch_c1e6"]["bytes"] < dense_at_c // 10)
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="auto",
                    choices=("auto", "northstar", "host", "smoke"))
    ap.add_argument("--rounds", type=int, default=0,
                    help="timed rounds per rep (0 = preset default)")
    ap.add_argument("--reps", type=int, default=3,
                    help="reps per arm; best-of wall is reported")
    ap.add_argument("--capture-run", default=None, metavar="DIR",
                    help="also run the full CLI loop once with "
                         "telemetry default into this run dir "
                         "(metrics.jsonl + trace.json artifacts)")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()

    import jax

    from fedtorch_tpu.telemetry import Telemetry
    from fedtorch_tpu.utils.tracing import fetch_sync

    preset = args.preset
    if preset == "auto":
        preset = "northstar" if jax.default_backend() == "tpu" else "host"
    rounds = args.rounds or {"northstar": 30, "host": 40, "smoke": 6}[
        preset]
    log(f"devices: {jax.devices()}  preset={preset} rounds={rounds} "
        f"reps={args.reps}")

    cfg, data = build_workload(preset)
    trainer = make_trainer(cfg, data)
    # warmup: compile the round program once, fully drained
    s, c = trainer.init_state(jax.random.key(6))
    s, c, _ = trainer.run_round(s, c)
    fetch_sync(s.params)

    # the cohort arm runs its own trainer: cohort_stats changes the
    # traced program (per-client outputs at the aggregation seam), so
    # the arm measures the WHOLE federation-plane observability cost —
    # in-program stats + the [k] vectors on the fetch + the ledger
    # fold + the extra row gauges — against the same <= 1% bar
    import dataclasses
    cfg_cohort = dataclasses.replace(
        cfg, telemetry=dataclasses.replace(cfg.telemetry,
                                           cohort_stats=True))
    trainer_cohort = make_trainer(cfg_cohort, data)
    s2, c2 = trainer_cohort.init_state(jax.random.key(6))
    s2, c2, _ = trainer_cohort.run_round(s2, c2)
    fetch_sync(s2.params)
    del s2, c2

    import tempfile

    # the costs arm: program_costs captured ONCE up front (the real
    # CLI loop pays that once at round 1, outside steady state), then
    # every row additionally carries the measured-MFU + HBM-watermark
    # gauges — the RECURRING per-round cost this arm measures against
    # the same <=1% bar (ISSUE 8)
    from fedtorch_tpu.telemetry.costs import ProgramCostCapture
    cost_cap = ProgramCostCapture(
        tempfile.mkdtemp(prefix="telemetry_ab_costs_"),
        compute_dtype="float32", arch=cfg.model.arch,
        batch_size=cfg.data.batch_size, local_steps=trainer.local_steps,
        k_online=trainer.k_online,
        num_devices=int(trainer.mesh.devices.size),
        backend=jax.default_backend(), log=log)
    s0, c0 = trainer.init_state(jax.random.key(6))
    programs, primary = trainer.lowered_cost_programs(s0, c0)
    cost_cap.capture(programs, primary=primary)
    del s0, c0

    # cohort_off = the cohort-stats PROGRAM under DEFAULT telemetry
    # with no federation-plane emission: the cohort arm's baseline.
    # cohort_stats changes the traced program (in-jit statistics at
    # the aggregation seam) and default telemetry has its own
    # separately-measured bar (the 'default' arm), so cohort vs
    # cohort_off isolates exactly what ISSUE 14's <= 1% bar governs:
    # the [k] cohort vectors riding the fetch + the ledger fold + the
    # cohort row gauges. The program change itself is reported as
    # program_frac_vs_off (informational: round compute, not
    # telemetry; a vision-scale round amortizes it where this
    # tiny-MLP arm cannot)
    levels = ("off", "default", "costs", "cohort_off", "cohort",
              "debug")
    walls = {lv: [] for lv in levels}
    # reps INTERLEAVED across arms: slow host-noise drift (another
    # tenant, thermal state) then biases every arm equally instead of
    # landing on whichever arm ran last; best-of-reps per arm rejects
    # the one-sided noise that remains
    for rep in range(args.reps):
        for level in levels:
            run_dir = tempfile.mkdtemp(prefix=f"telemetry_ab_{level}_")
            tel = Telemetry(None if level == "off" else run_dir,
                            level="default" if level in (
                                "costs", "cohort", "cohort_off")
                            else level)
            tel.install()
            try:
                if level == "cohort":
                    from fedtorch_tpu.telemetry.ledger import (
                        ClientLedger,
                    )
                    led_obj = ClientLedger(
                        run_dir,
                        num_clients=cfg.federated.num_clients)
                    wall = timed_loop(trainer_cohort, rounds, tel,
                                      run_dir, ledger=led_obj)
                elif level == "cohort_off":
                    wall = timed_loop(trainer_cohort, rounds, tel,
                                      run_dir)
                else:
                    wall = timed_loop(
                        trainer, rounds, tel, run_dir,
                        cost_cap=cost_cap if level == "costs"
                        else None)
            finally:
                tel.close()
            walls[level].append(wall)
            log(f"  rep{rep} {level}: {wall / rounds * 1e3:.3f} "
                "ms/round")
    arms = {lv: {"wall_s": min(walls[lv]),
                 "per_round_s": min(walls[lv]) / rounds,
                 "reps_ms_per_round": [round(w / rounds * 1e3, 3)
                                       for w in walls[lv]]}
            for lv in levels}

    base = arms["off"]["per_round_s"]
    for level in ("default", "costs", "debug"):
        arms[level]["overhead_frac"] = \
            (arms[level]["per_round_s"] - base) / base
    cbase = arms["cohort_off"]["per_round_s"]
    arms["cohort"]["overhead_frac"] = \
        (arms["cohort"]["per_round_s"] - cbase) / cbase
    # informational: the cohort PROGRAM + default telemetry vs the
    # bare off arm (round compute the stats add, not telemetry cost)
    arms["cohort_off"]["baseline_frac_vs_off"] = (cbase - base) / base
    # the cohort bar is JUDGED on the paired per-leg measurement: the
    # federation-plane additions are microseconds (vector-fetch delta
    # + ledger fold + gauge row surplus) and a whole-round A/B on a
    # shared 1-core box carries a multi-percent noise envelope that
    # swamps them — overhead_frac above stays recorded as the
    # (noise-bound) A/B evidence, host_frac_measured is the verdict
    uc = unit_costs()
    fetch_delta = cohort_fetch_delta_us(trainer_cohort)
    cohort_host_us = fetch_delta + uc["ledger_fold_us"] \
        + uc["metrics_row_us"]
    arms["cohort"]["fetch_delta_us"] = round(fetch_delta, 2)
    arms["cohort"]["host_us_per_round"] = round(cohort_host_us, 2)
    arms["cohort"]["host_frac_measured"] = \
        cohort_host_us * 1e-6 / cbase
    led_mem = ledger_memory()
    # the ops-plane gauges (ISSUE 15) ride the costs/default arms
    # above (timed_loop now runs the overlap tracker like the CLI
    # loop); the paired per-leg verdict is the derivation's own
    # measured microseconds against the off baseline
    ops = {"overlap_derive_us": uc["overlap_derive_us"],
           "host_frac_measured": uc["overlap_derive_us"] * 1e-6 / base}
    ok = (arms["default"]["overhead_frac"] <= ACCEPT_OVERHEAD
          and arms["costs"]["overhead_frac"] <= ACCEPT_OVERHEAD
          and arms["cohort"]["host_frac_measured"] <= ACCEPT_OVERHEAD
          and ops["host_frac_measured"] <= ACCEPT_OVERHEAD
          and led_mem["bounded"])

    result = {
        "preset": preset,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "rounds": rounds,
        "reps": args.reps,
        "arms": arms,
        "unit_costs": uc,
        "ops_gauges": ops,
        "ledger_memory": led_mem,
        "accept_overhead_frac": ACCEPT_OVERHEAD,
        "pass": bool(ok),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
    log(f"off {base * 1e3:.3f} ms/round; default "
        f"{arms['default']['per_round_s'] * 1e3:.3f} ms/round "
        f"({arms['default']['overhead_frac'] * 100:+.3f}%); costs "
        f"{arms['costs']['overhead_frac'] * 100:+.3f}%; cohort "
        f"{arms['cohort']['host_frac_measured'] * 100:+.4f}% measured "
        f"({arms['cohort']['host_us_per_round']} us/round; A/B arm "
        f"{arms['cohort']['overhead_frac'] * 100:+.2f}%, baseline "
        f"{arms['cohort_off']['baseline_frac_vs_off'] * 100:+.2f}% vs "
        "off); debug "
        f"{arms['debug']['overhead_frac'] * 100:+.3f}%  "
        f"ledger@1e6 {led_mem['sketch_c1e6']['bytes']} B  pass={ok}")
    log(f"wrote {args.out}")

    if args.capture_run:
        # the artifact leg: one telemetry-on pass over the SAME
        # workload into a persistent run dir — metrics.jsonl +
        # trace.json (Perfetto) land as capture artifacts without a
        # dataset loader (the north-star data here is synthetic by
        # construction; zero-egress container)
        os.makedirs(args.capture_run, exist_ok=True)
        cap_rounds = min(rounds, 10)
        tel = Telemetry(args.capture_run, level="default",
                        run_meta={"preset": preset,
                                  "source": "telemetry_bench"})
        tel.install()
        try:
            timed_loop(trainer, cap_rounds, tel, args.capture_run)
            tel.health_update("complete", round_idx=cap_rounds)
        finally:
            tel.close()
        log(f"capture run -> {args.capture_run} "
            f"({cap_rounds} rounds of metrics.jsonl + trace.json)")
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
