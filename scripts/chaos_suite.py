"""Chaos suite: short FedAvg + SCAFFOLD synthetic jobs under a fault
schedule, asserting the faulted run stays within an accuracy tolerance
of the fault-free run (ISSUE 1 acceptance: drop_rate=0.25 must complete
every round host-exception-free with final top-1 within 5 points).

Each algorithm trains twice from the same seed — once fault-free, once
under the chaos schedule (client crashes + stragglers + NaN-poisoned
uploads with the update guards on, all deterministic under the threaded
PRNG) — and the gap in final test accuracy is checked against the
tolerance. The supervisor wraps the faulted run, so a diverged round
would roll back instead of killing the job.

Registered as a `slow`-marked pytest (tests/test_chaos_suite.py) so the
tier-1 fast lane stays fast. Standalone usage:

    python scripts/chaos_suite.py [--rounds N] [--smoke] [--tol PTS]
    python scripts/chaos_suite.py --attack-matrix   # -> ATTACK_AB.json

`--attack-matrix` (ISSUE 9) runs the byzantine attack x robust
aggregator grid: each cell trains under an adversary schedule
(`fault.byzantine_*`) with one `--robust_agg` rule and is scored
against the fault-free baseline. Plain `mean` is the NEGATIVE CONTROL:
the acceptance bar requires the attack to break it (> tol points lost
under 25% sign_flip) while at least one robust rule holds within tol —
proving both that the attack bites and that the defense works.
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


def straggler_heavy_fault() -> dict:
    """The straggler-heavy chaos schedule (FaultConfig kwargs): a
    long-tail delay distribution — 40% of dispatches land in a 10x
    tail. Under the SYNC planes these knobs cut straggler step budgets
    (the deadline model); under the async commit plane the SAME knobs
    draw the event scheduler's completion delays, so one preset drives
    both planes. Returned as kwargs so importers can compose
    it into a FaultConfig with guards/crashes of their own."""
    return {"straggler_rate": 0.4, "straggler_step_frac": 0.1}


def run_suite(rounds: int = 20, smoke: bool = False, tol_points: float = 5.0,
              algorithms=("fedavg", "scaffold"), seed: int = 0,
              straggler_heavy: bool = False) -> dict:
    """Returns the suite report; raises AssertionError on a tolerance
    breach (the pytest wrapper surfaces it directly).

    ``straggler_heavy=True`` switches the drill: instead of fault-free
    vs chaos on the SYNC plane, each algorithm runs sync vs ASYNC
    (``sync_mode='async'``) under the :func:`straggler_heavy_fault`
    schedule — the ISSUE 6 convergence bar (async within ``tol_points``
    of sync while its commit program traces exactly once)."""
    import jax
    import jax.numpy as jnp

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, evaluate
    from fedtorch_tpu.robustness import RoundSupervisor
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    C = 8 if smoke else 16
    B = 16 if smoke else 32
    K = 3 if smoke else 5
    rounds = max(rounds, 4)
    # async needs num_clients >= concurrency + buffer so every arrival
    # has a distinct replacement; half-rate participation keeps the
    # smoke shapes legal while leaving the sync leg a real cohort
    online_rate = 0.5 if straggler_heavy else 1.0

    fault_schedule = FaultConfig(
        client_drop_rate=0.25, straggler_rate=0.25,
        straggler_step_frac=0.5, nan_inject_rate=0.1,
        guard_updates=True, max_retries=2, backoff_base_s=0.0)
    if straggler_heavy:
        fault_schedule = FaultConfig(**straggler_heavy_fault())

    def one_run(algorithm: str, fault: FaultConfig,
                sync_mode: str = "sync", num_comms: int = None):
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=30,
                            batch_size=B, synthetic_alpha=0.5,
                            synthetic_beta=0.5),
            federated=FederatedConfig(
                federated=True, num_clients=C,
                num_comms=num_comms or rounds,
                online_client_rate=online_rate, algorithm=algorithm,
                sync_type="local_step", sync_mode=sync_mode),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=K),
            fault=fault,
        ).finalize()
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=B)
        if sync_mode == "async":
            from fedtorch_tpu.async_plane import AsyncFederatedTrainer
            trainer = AsyncFederatedTrainer(cfg, model,
                                            make_algorithm(cfg),
                                            data.train)
        else:
            trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                                       data.train)
        server, clients = trainer.init_state(jax.random.key(seed))
        sup = RoundSupervisor(trainer, sleep_fn=lambda s: None)
        counters = {"dropped": 0.0, "stragglers": 0.0, "rejected": 0.0,
                    "retraces": 0}
        # first round/commit pays the (expected) trace; the sentinel
        # then proves the program re-traces ZERO times — the async
        # commit program is trace-once like every other plane

        def count(m):
            counters["dropped"] += float(m.dropped_clients)
            counters["stragglers"] += float(m.straggler_clients)
            counters["rejected"] += float(m.rejected_updates)

        server, clients, m = sup.run_round(server, clients)
        count(m)
        with RecompilationSentinel() as sentinel:
            for _ in range(cfg.federated.num_comms - 1):
                server, clients, m = sup.run_round(server, clients)
                count(m)
        counters["retraces"] = sum(sentinel.counts.values())
        trainer.invalidate_stream()
        assert all(bool(jnp.all(jnp.isfinite(x)))
                   for x in jax.tree.leaves(server.params)), \
            f"{algorithm}: non-finite server params survived the guards"
        res = evaluate(model, server.params, data.test_x, data.test_y)
        return float(res.top1), counters, sup.stats

    report = {"rounds": rounds, "clients": C, "tol_points": tol_points,
              "fault": straggler_heavy_fault() if straggler_heavy else
              {"client_drop_rate": 0.25, "straggler_rate": 0.25,
               "nan_inject_rate": 0.1, "guard": "reject"},
              "mode": "straggler_heavy_sync_vs_async"
              if straggler_heavy else "clean_vs_chaos",
              "algorithms": {}}
    t0 = time.time()
    for algorithm in algorithms:
        if straggler_heavy:
            # the async convergence bar: sync vs async under the same
            # long-tail schedule, equal CLIENT-UPDATE budget (R sync
            # rounds aggregate k updates each; the async buffer holds
            # m = k // 2, so it commits twice as often)
            sync_acc, _, _ = one_run(algorithm, fault_schedule, "sync")
            k = max(int(online_rate * C), 1)
            commits = rounds * k // max(k // 2, 1)
            async_acc, counters, stats = one_run(
                algorithm, fault_schedule, "async", num_comms=commits)
            gap = (sync_acc - async_acc) * 100.0
            entry = {
                "sync_top1": round(sync_acc, 4),
                "async_top1": round(async_acc, 4),
                "gap_points": round(gap, 2),
                "async_commits": commits,
                "async_stragglers": int(counters["stragglers"]),
                "commit_retraces": counters["retraces"],
            }
            report["algorithms"][algorithm] = entry
            log(f"{algorithm}: sync {sync_acc:.4f} async {async_acc:.4f}"
                f" gap {gap:+.2f}pts over {commits} commits "
                f"({entry['async_stragglers']} stragglers)")
            assert counters["stragglers"] > 0, \
                f"{algorithm}: straggler-heavy schedule delayed nothing"
            assert counters["retraces"] == 0, (
                f"{algorithm}: async commit program retraced "
                f"{counters['retraces']}x mid-run (trace-once bar)")
            assert gap <= tol_points, (
                f"{algorithm}: async lost {gap:.2f} accuracy points vs "
                f"sync (tolerance {tol_points}); ISSUE 6 regression")
            continue
        clean_acc, _, _ = one_run(algorithm, FaultConfig())
        chaos_acc, counters, stats = one_run(algorithm, fault_schedule)
        gap = (clean_acc - chaos_acc) * 100.0
        entry = {
            "clean_top1": round(clean_acc, 4),
            "chaos_top1": round(chaos_acc, 4),
            "gap_points": round(gap, 2),
            "faults_injected": {k: int(v) for k, v in counters.items()
                                if k != "retraces"},
            "supervisor": {"rollbacks": stats.rollbacks,
                           "skipped_rounds": stats.skipped_rounds},
        }
        report["algorithms"][algorithm] = entry
        log(f"{algorithm}: clean {clean_acc:.4f} chaos {chaos_acc:.4f} "
            f"gap {gap:+.2f}pts faults {entry['faults_injected']}")
        assert counters["dropped"] > 0, \
            f"{algorithm}: chaos schedule injected no crashes"
        assert counters["rejected"] > 0, \
            f"{algorithm}: guards rejected nothing despite NaN injection"
        assert gap <= tol_points, (
            f"{algorithm}: chaos run lost {gap:.2f} accuracy points "
            f"(tolerance {tol_points}); robustness regression")
    report["wall_seconds"] = round(time.time() - t0, 1)
    return report


def run_availability_matrix(rounds: int = 12, smoke: bool = False,
                            seed: int = 0, out_path: str = None) -> dict:
    """The deployment-realism drill (docs/robustness.md §7) →
    AVAIL_AB.json. Four legs:

    * ``default_bitwise`` — the async scheduler under the ``default``
      availability model must reproduce the RAW legacy straggler-knob
      fold chain bitwise (recomputed inline here, independently of
      `robustness/availability.py`), so the model refactor cannot have
      moved a single draw.
    * ``trace_replay`` — the armed sync lifecycle (trace model,
      over-selection, deadline, quorum) run twice from one seed:
      per-round server-param sha256 fingerprints identical, lifecycle
      counters active, round program traced exactly once.
    * ``degrade_vs_abort`` — at 95% dropout under a 0.9 quorum the
      ``degrade`` action completes EVERY round (degraded, never
      wedged — a naive deadline abort would stall the run), while the
      ``abort`` action escalates into the supervisor's reseeded
      retry → skip-with-cause path.
    * ``async_dropout`` — the async commit loop under trace-model
      dropouts: arrivals discarded + re-dispatched, commit sequence
      deterministic under replay.
    """
    import hashlib

    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    from fedtorch_tpu.robustness import RoundSupervisor
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    C = 8 if smoke else 16
    B = 16 if smoke else 32
    rounds = max(rounds, 6)
    t0 = time.time()
    report = {"rounds": rounds, "clients": C, "seed": seed, "legs": {}}

    def fingerprint(tree) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(tree):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()[:16]

    def make_cfg(fault: FaultConfig, sync_mode: str = "sync",
                 num_comms: int = None):
        return ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=30,
                            batch_size=B, synthetic_alpha=0.5,
                            synthetic_beta=0.5),
            federated=FederatedConfig(
                federated=True, num_clients=C,
                num_comms=num_comms or rounds,
                online_client_rate=0.5, algorithm="fedavg",
                sync_type="local_step", sync_mode=sync_mode),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=3),
            fault=fault,
        ).finalize()

    # -- leg 1: default model bitwise vs the raw legacy fold chain ------
    from fedtorch_tpu.async_plane.scheduler import AsyncSchedule
    from fedtorch_tpu.robustness.availability import LEGACY_DELAY_SALT

    rate, frac = 0.4, 0.1
    # lint: disable=FTL001 — offline harness setup, raw key bytes
    kd = np.asarray(jax.random.key_data(jax.random.key(seed)))
    impl = jax.random.key_impl(jax.random.key(seed))

    def make_sched():
        return AsyncSchedule(kd, impl, num_clients=C, concurrency=4,
                             buffer_size=2, ring_size=4,
                             straggler_rate=rate,
                             straggler_step_frac=frac)

    sched = make_sched()
    # dispatch 0's delay sits in the event heap as its finish time
    # (dispatched at now=0), before any commit pops it
    d0 = next(t for t, did, *_ in sched._heap if did == 0)
    # recompute it by hand off the RAW legacy chain — u = uniform(
    # fold(fold(key, SALT), dispatch_id), (2,)) on the cpu backend,
    # then the historical host-f64 tail math
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        k = jax.random.fold_in(jax.random.key(seed), LEGACY_DELAY_SALT)
        # lint: disable=FTL001 — the sync IS the measurement here
        u = np.asarray(jax.random.uniform(jax.random.fold_in(k, 0),
                                          (2,)), np.float64)
    base = 1.0 + 0.25 * u[1]
    want = base * (1.0 / frac) if u[0] < rate else base

    def commit_seq(s):
        return [(cm.commit, cm.idx.tolist(), cm.version.tolist(),
                 cm.arrival_times.tolist()) for cm in
                (s.next_commit() for _ in range(6))]

    seq = commit_seq(sched)
    seq2 = commit_seq(make_sched())
    # lint: disable=FTL001 — report scalars for the JSON artifact
    want_f, d0_f = float(want), float(d0)
    report["legs"]["default_bitwise"] = {
        "legacy_d0_recomputed": want_f,
        "scheduler_d0": d0_f,
        "d0_bitwise_match": want_f == d0_f,
        "replay_identical": seq == seq2,
        "commit_sequence_len": len(seq),
    }
    assert d0 == want, (
        f"default model moved the legacy delay chain: scheduler drew "
        f"{d0!r}, raw fold chain gives {want!r}")
    assert seq == seq2, "default-model commit sequence not replayable"

    # -- leg 2: armed sync lifecycle, bitwise replay + trace-once -------
    armed = FaultConfig(avail_model="trace", avail_dropout_rate=0.3,
                        avail_diurnal_period=8, over_select_frac=1.5,
                        avail_quorum_frac=0.5)

    def sync_run(fault, supervise=False, causes=None):
        cfg = make_cfg(fault)
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=B)
        t = FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)
        server, clients = t.init_state(jax.random.key(seed))
        run = t.run_round
        sup = None
        if supervise:
            sup = RoundSupervisor(
                t, sleep_fn=lambda s: None,
                on_round_skipped=(lambda r, c: causes.append(c))
                if causes is not None else None)
            run = sup.run_round
        fps, counters = [], {"avail_dropped": 0.0, "deadline_missed": 0.0,
                             "quorum_degraded": 0.0}
        server, clients, m = run(server, clients)
        with RecompilationSentinel() as sentinel:
            for _ in range(cfg.federated.num_comms - 1):
                server, clients, m = run(server, clients)
                for key_ in counters:
                    counters[key_] += float(getattr(m, key_))
                fps.append(fingerprint(server.params))
        retraces = sum(sentinel.counts.values())
        return fps, counters, retraces, sup, server

    fps_a, counters, retraces, _, _ = sync_run(armed)
    fps_b, _, _, _, _ = sync_run(armed)
    report["legs"]["trace_replay"] = {
        "fingerprints_identical": fps_a == fps_b,
        "avail_dropped": int(counters["avail_dropped"]),
        "deadline_missed": int(counters["deadline_missed"]),
        "retraces": retraces,
    }
    assert fps_a == fps_b, \
        "armed trace-model trajectories not seeded-replayable"
    assert counters["avail_dropped"] + counters["deadline_missed"] > 0, \
        "armed lifecycle injected nothing"
    assert retraces == 0, (
        f"armed round program retraced {retraces}x — over-selection/"
        "deadline masking broke trace-once")

    # -- leg 3: sub-quorum degrade completes; abort escalates -----------
    heavy = dict(avail_model="trace", avail_dropout_rate=0.95,
                 avail_diurnal_period=4, over_select_frac=1.5,
                 avail_quorum_frac=0.9)
    _, deg_counters, _, _, deg_server = sync_run(FaultConfig(**heavy))
    deg_rounds = int(jax.device_get(deg_server.round))
    causes = []
    _, _, _, sup, ab_server = sync_run(
        FaultConfig(supervisor=True, max_retries=1, backoff_base_s=0.0,
                    avail_quorum_action="abort", **heavy),
        supervise=True, causes=causes)
    ab_rounds = int(jax.device_get(ab_server.round))
    report["legs"]["degrade_vs_abort"] = {
        "degrade_rounds_completed": deg_rounds,
        "degraded_rounds": int(deg_counters["quorum_degraded"]),
        "abort_rounds_completed": ab_rounds,
        "abort_skipped_quorum": sup.stats.skipped_quorum,
        "abort_skip_causes": sorted(set(causes)),
    }
    assert deg_rounds == rounds, (
        f"degrade leg wedged at round {deg_rounds}/{rounds} — "
        "sub-quorum rounds must complete degraded")
    assert deg_counters["quorum_degraded"] > 0, \
        "degrade leg never went sub-quorum at 95% dropout"
    assert ab_rounds == rounds, "abort leg wedged the round counter"
    assert sup.stats.skipped_quorum > 0 and causes, \
        "abort leg never escalated a sub-quorum round"
    assert set(causes) == {"quorum"}, f"unexpected skip causes {causes}"

    # -- leg 4: async trace-model dropouts, deterministic ---------------
    def async_run():
        cfg = make_cfg(FaultConfig(avail_model="trace",
                                   avail_dropout_rate=0.3,
                                   **straggler_heavy_fault()),
                       sync_mode="async", num_comms=rounds)
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=B)
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        t = AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                  data.train)
        server, clients = t.init_state(jax.random.key(seed))
        for _ in range(rounds):
            server, clients, m = t.run_round(server, clients)
        st = t.schedule_stats  # grab before invalidate clears the sim
        t.invalidate_stream()
        return fingerprint(server.params), st

    fp1, st1 = async_run()
    fp2, st2 = async_run()
    report["legs"]["async_dropout"] = {
        "fingerprint_identical": fp1 == fp2,
        "dropouts": st1.dropouts,
    }
    assert fp1 == fp2, "async trace-model run not seeded-replayable"
    assert st1.dropouts > 0, "async availability model dropped nothing"
    assert st1.dropouts == st2.dropouts, \
        "async dropout count not deterministic"

    report["wall_seconds"] = round(time.time() - t0, 1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        log(f"wrote {out_path}")
    return report


def run_privacy_matrix(rounds: int = 12, smoke: bool = False,
                       seed: int = 0, out_path: str = None) -> dict:
    """The privacy-plane drill (docs/robustness.md §8) → DP_AB.json.
    Five legs:

    * ``off_identical`` — the DP-off build is the pre-PR program:
      lowered round HLO byte-identical across disarmed DP knob
      settings, server.aux unwrapped, no dp_* metrics fields, and the
      off trajectory bitwise-replayable.
    * ``closed_form_control`` — the stdlib RDP accountant within 1%
      of the continuous closed-form ε on the pure-Gaussian
      no-subsampling control, and subsampling strictly amplifies.
    * ``frontier`` — the measured ε-vs-accuracy frontier at
      ε ∈ {2, 8, ∞} (δ fixed): noise calibrated by bisection against
      the accountant itself, every armed cell bitwise-replayable and
      traced exactly once, spend within budget.
    * ``layered`` — DP × trimmed_mean × byzantine cohort: the layered
      defense completes every round with finite params while both the
      robust rule and the clip+noise stage fire.
    * ``exhaustion`` — both budget actions drilled through the real
      CLI loop: ``stop`` ends at the last affordable round with a
      `complete` intent + `privacy.budget_exhausted` event; `degrade`
      finishes every round noise-free with a `degraded` intent.
      Neither wedges.
    """
    import hashlib
    import shutil
    import tempfile

    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig,
        FederatedConfig, ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    from fedtorch_tpu.robustness.privacy import (
        PrivacyAccountant, calibrate_noise_multiplier,
        closed_form_epsilon,
    )
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    C = 8 if smoke else 16
    B = 16 if smoke else 32
    rounds = max(rounds, 6)
    delta = 1e-5
    t0 = time.time()
    report = {"rounds": rounds, "clients": C, "seed": seed,
              "delta": delta, "legs": {}}

    def fingerprint(tree) -> str:
        h = hashlib.sha256()
        for leaf in jax.tree.leaves(tree):
            h.update(np.asarray(leaf).tobytes())
        return h.hexdigest()[:16]

    def make_cfg(fault: FaultConfig, num_comms: int = None,
                 run_dir: str = None):
        return ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=30,
                            batch_size=B, synthetic_alpha=0.5,
                            synthetic_beta=0.5),
            federated=FederatedConfig(
                federated=True, num_clients=C,
                num_comms=num_comms or rounds,
                online_client_rate=0.5, algorithm="fedavg",
                sync_type="local_step", sync_mode="sync"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=3),
            checkpoint=CheckpointConfig(run_dir=run_dir, debug=False)
            if run_dir else CheckpointConfig(),
            fault=fault,
        ).finalize()

    def make_trainer(fault: FaultConfig):
        cfg = make_cfg(fault)
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=B)
        return FederatedTrainer(cfg, model, make_algorithm(cfg),
                                data.train)

    def dp_run(fault: FaultConfig):
        """rounds sync rounds; per-round fingerprints + tail accuracy
        + dp gauges, trace count."""
        t = make_trainer(fault)
        server, clients = t.init_state(jax.random.key(seed))
        fps, accs, gauges = [], [], {}
        totals = {"byzantine": 0.0, "robust_trimmed": 0.0}
        with RecompilationSentinel() as sentinel:
            for _ in range(rounds):
                server, clients, m = t.run_round(server, clients)
                sc = t.round_host_scalars(clients, m)
                accs.append(sc["acc_sum"] / max(sc["n_online"], 1.0))
                fps.append(fingerprint(server.params))
                for key_ in totals:
                    totals[key_] += sc[key_]
                gauges = {k: sc[k] for k in
                          ("dp_clipped_frac", "dp_noise_sigma")
                          if k in sc}
        return (fps, sum(accs[-3:]) / 3, gauges,
                sum(sentinel.counts.values()), server, m, totals)

    # -- leg 1: DP off IS the pre-PR program ----------------------------
    def lowered(fault: FaultConfig) -> str:
        t = make_trainer(fault)
        server, clients = t.init_state(jax.random.key(seed))
        return t._round_jit.lower(server, clients, t.data,
                                  t.val_data).as_text()

    hlo_plain = lowered(FaultConfig())
    # disarmed DP knobs at non-default values must not reach the
    # lowered program (static-config contract)
    hlo_disarmed = lowered(FaultConfig(dp_noise_multiplier=0.0,
                                       dp_clip_norm=9.0, dp_delta=0.5,
                                       dp_budget_action="degrade"))
    t_off = make_trainer(FaultConfig())
    s_off, _ = t_off.init_state(jax.random.key(seed))
    fps_off, acc_off, g_off, tr_off, _, m_off, _ = dp_run(FaultConfig())
    fps_off2 = dp_run(FaultConfig())[0]
    report["legs"]["off_identical"] = {
        "hlo_bytes": len(hlo_plain),
        "hlo_byte_identical": hlo_plain == hlo_disarmed,
        "aux_unwrapped": not (isinstance(s_off.aux, dict)
                              and "dp_noise_scale" in s_off.aux),
        "no_dp_metrics": m_off.dp_clipped_frac is None
        and "dp_clipped_frac" not in g_off,
        "replay_identical": fps_off == fps_off2,
        "retraces": tr_off - 1,
    }
    assert hlo_plain == hlo_disarmed, \
        "disarmed DP knobs leaked into the lowered round program"
    assert m_off.dp_clipped_frac is None, \
        "DP-off round emitted dp metrics fields"
    assert fps_off == fps_off2, "off leg not bitwise-replayable"

    # -- leg 2: accountant vs closed form -------------------------------
    z_ctl, T_ctl = 1.1, 100
    acc_ctl = PrivacyAccountant(z_ctl, delta)
    acc_ctl.charge(1.0, rounds=T_ctl)
    eps_grid = acc_ctl.epsilon()
    eps_cf = closed_form_epsilon(z_ctl, T_ctl, delta)
    rel = abs(eps_grid - eps_cf) / eps_cf
    sub = PrivacyAccountant(z_ctl, delta)
    sub.charge(0.25, rounds=T_ctl)
    report["legs"]["closed_form_control"] = {
        "noise_multiplier": z_ctl, "rounds": T_ctl,
        "epsilon_accounted": eps_grid, "epsilon_closed_form": eps_cf,
        "rel_error": rel,
        "epsilon_subsampled_q0.25": sub.epsilon(),
    }
    assert rel < 0.01, (
        f"accountant {eps_grid} vs closed form {eps_cf}: rel {rel}")
    assert sub.epsilon() < eps_grid, "subsampling did not amplify"

    # -- leg 3: the eps-vs-accuracy frontier ----------------------------
    q = min(1.0, (C // 2) / C)  # online_client_rate=0.5 cohort
    clip = 0.5
    frontier = []
    for eps_target in (2.0, 8.0, float("inf")):
        if eps_target == float("inf"):
            fault = FaultConfig()
            z = 0.0
        else:
            z = calibrate_noise_multiplier(eps_target, rounds, q,
                                           delta)
            fault = FaultConfig(dp_noise_multiplier=z,
                                dp_clip_norm=clip, dp_delta=delta)
        fps1, acc1, gauges, traces = dp_run(fault)[:4]
        fps2 = dp_run(fault)[0]
        spent = None
        if z > 0.0:
            a = PrivacyAccountant(z, delta)
            a.charge(q, rounds=rounds)
            spent = a.epsilon()
        cell = {"epsilon_target": eps_target if eps_target != float(
            "inf") else "inf",
            "noise_multiplier": z, "epsilon_spent": spent,
            "final_acc": acc1, "gauges": gauges,
            "replay_identical": fps1 == fps2,
            "retraces": traces - 1}
        frontier.append(cell)
        assert fps1 == fps2, \
            f"eps={eps_target} cell not bitwise-replayable"
        assert traces == 1, \
            f"eps={eps_target} cell traced {traces}x"
        if spent is not None:
            assert spent <= eps_target * 1.001, (
                f"calibrated z={z} overspent: {spent} > {eps_target}")
    report["legs"]["frontier"] = frontier

    # -- leg 4: DP x trimmed_mean x byzantine cohort --------------------
    z8 = calibrate_noise_multiplier(8.0, rounds, q, delta)
    layered = FaultConfig(dp_noise_multiplier=z8, dp_clip_norm=clip,
                          dp_delta=delta, robust_agg="trimmed_mean",
                          robust_trim_frac=0.25, byzantine_rate=0.25,
                          byzantine_mode="sign_flip",
                          byzantine_scale=3.0)
    fps1, acc_l, g_l, traces, server_l, _, tot_l = dp_run(layered)
    fps2 = dp_run(layered)[0]
    finite = all(np.isfinite(np.asarray(x)).all()
                 for x in jax.tree.leaves(server_l.params))
    report["legs"]["layered"] = {
        "noise_multiplier": z8, "final_acc": acc_l,
        "robust_trimmed_total": tot_l["robust_trimmed"],
        "byzantine_total": tot_l["byzantine"],
        "dp_gauges": g_l, "params_finite": finite,
        "replay_identical": fps1 == fps2, "retraces": traces - 1,
    }
    assert fps1 == fps2 and traces == 1, "layered cell broke contracts"
    assert finite, "layered defense diverged to non-finite params"
    assert tot_l["byzantine"] > 0, "adversary never fired"
    assert tot_l["robust_trimmed"] > 0, "trimmed_mean never trimmed"
    assert g_l.get("dp_noise_sigma", 0.0) > 0, "DP noise not applied"

    # -- leg 5: budget exhaustion drills (real CLI loop) ----------------
    from fedtorch_tpu.cli import run_experiment
    from fedtorch_tpu.telemetry import read_health
    from fedtorch_tpu.telemetry.schema import iter_jsonl

    z_ex = 1.0
    half = rounds // 2
    affordable = PrivacyAccountant(z_ex, delta)
    affordable.charge(q, rounds=half)
    budget = affordable.epsilon() * 1.0001  # affords exactly `half`
    exdrills = {}
    for action in ("stop", "degrade"):
        run_root = tempfile.mkdtemp(prefix=f"dp_{action}_")
        run_dir = os.path.join(run_root, "run")
        cfg = make_cfg(FaultConfig(dp_noise_multiplier=z_ex,
                                   dp_clip_norm=clip, dp_delta=delta,
                                   dp_epsilon_budget=budget,
                                   dp_budget_action=action),
                       run_dir=run_dir)
        res = run_experiment(cfg)
        events = [e for e in iter_jsonl(
            os.path.join(run_dir, "events.jsonl"))
            if e.get("event") == "privacy.budget_exhausted"]
        rows = [r for r in iter_jsonl(
            os.path.join(run_dir, "metrics.jsonl")) if "round" in r]
        intent = read_health(run_dir)["intent"]
        with open(os.path.join(run_dir,
                               "privacy_accountant.json")) as f:
            acc_doc = json.load(f)
        exdrills[action] = {
            "rounds_completed": len(rows),
            "exhausted_at_round": res.get("dp_exhausted_at_round"),
            "intent": intent, "events": len(events),
            "epsilon_spent": acc_doc["epsilon_spent"],
            "epsilon_budget": budget,
            "sigma_tail": rows[-1]["dp_noise_sigma"] if rows else None,
        }
        assert len(events) == 1 and events[0]["action"] == action, \
            f"{action}: budget event missing/mislabelled"
        assert acc_doc["epsilon_spent"] <= budget * 1.0001, \
            f"{action}: overspent the budget"
        if action == "stop":
            assert intent == "complete", \
                f"stop drill exited intent={intent}, want complete"
            assert len(rows) == half == res["dp_exhausted_at_round"], (
                f"stop drill ran {len(rows)} rounds, want {half}")
        else:
            assert intent == "degraded", \
                f"degrade drill exited intent={intent}, want degraded"
            assert len(rows) == rounds, \
                f"degrade drill wedged at {len(rows)}/{rounds}"
            assert rows[-1]["dp_noise_sigma"] == 0.0, \
                "degrade tail still noising"
            assert rows[half - 1]["dp_noise_sigma"] > 0.0, \
                "pre-exhaustion rounds were not noised"
        shutil.rmtree(run_root, ignore_errors=True)
    report["legs"]["exhaustion"] = exdrills

    report["wall_seconds"] = round(time.time() - t0, 1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        log(f"wrote {out_path}")
    return report


def run_builder_matrix(rounds: int = 8, smoke: bool = False,
                       seed: int = 0, out_path: str = None) -> dict:
    """Round-program-builder smoke (ISSUE 11): three representative
    cells of the (source x dispatch x execution) matrix under the
    chaos schedule with guards ON — the composition the builder must
    keep working, on the real platform the capture step runs on:

    * ``resident x scan x vmap`` — the single-dispatch fast path;
    * ``feed x scan x vmap`` — the NEW scanned streamed program;
    * ``feed x commit x vmap`` — the async commit over the
      commit-keyed feed producer.

    Each cell must complete every dispatch host-exception-free with
    finite params, trace exactly once (zero retraces past warmup),
    and — the engine-wide bar — match its reference program BITWISE:
    the faulted per-round device program for the sync cells, the
    faulted resident commit program for the commit cell. Writes
    BUILDER_MATRIX.json."""
    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    from fedtorch_tpu.parallel.round_program import cell_name
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    C = 12 if smoke else 16
    B = 16 if smoke else 32
    K = 3 if smoke else 5
    rounds = max(rounds, 4)
    rounds -= rounds % 2  # scan chunks of 2
    fault = FaultConfig(
        client_drop_rate=0.25, straggler_rate=0.25,
        straggler_step_frac=0.5, nan_inject_rate=0.1,
        guard_updates=True, max_retries=2, backoff_base_s=0.0)

    def make_trainer(source, dispatch):
        sync_mode = "async" if dispatch == "commit" else "sync"
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=30,
                            batch_size=B, synthetic_alpha=0.5,
                            synthetic_beta=0.5,
                            data_plane="stream" if source == "feed"
                            else "device"),
            federated=FederatedConfig(
                federated=True, num_clients=C, num_comms=rounds,
                online_client_rate=0.5, algorithm="fedavg",
                sync_type="local_step", sync_mode=sync_mode),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=K),
            fault=fault,
        ).finalize()
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=B)
        if sync_mode == "async":
            from fedtorch_tpu.async_plane import AsyncFederatedTrainer
            return AsyncFederatedTrainer(cfg, model,
                                         make_algorithm(cfg),
                                         data.train)
        return FederatedTrainer(cfg, model, make_algorithm(cfg),
                                data.train)

    def run_cell(source, dispatch):
        trainer = make_trainer(source, dispatch)
        server, clients = trainer.init_state(jax.random.key(seed))
        t0 = time.time()
        metrics = []
        with RecompilationSentinel() as sentinel:
            if dispatch == "scan":
                for _ in range(rounds // 2):
                    server, clients, ms = trainer.run_rounds(
                        server, clients, 2)
                    metrics.append(jax.tree.map(np.asarray, ms))
                stacked = jax.tree.map(
                    lambda *xs: np.concatenate(xs, axis=0), *metrics)
            else:
                for _ in range(rounds):
                    server, clients, m = trainer.run_round(server,
                                                           clients)
                    metrics.append(jax.tree.map(np.asarray, m))
                stacked = jax.tree.map(
                    lambda *xs: np.stack(xs), *metrics)
            jax.block_until_ready(jax.tree.leaves(server.params))
        wall = time.time() - t0
        # one warmup trace per program is expected; anything more is a
        # retrace (the trace-once bar)
        retraces = max(sum(sentinel.counts.values()) - 1, 0)
        params = jax.device_get(server.params)
        trainer.invalidate_stream()
        finite = all(bool(np.all(np.isfinite(np.asarray(x))))
                     for x in jax.tree.leaves(params))
        return params, stacked, retraces, finite, wall

    cells = [("resident", "scan", "vmap"), ("feed", "scan", "vmap"),
             ("feed", "commit", "vmap")]
    # the references: faulted per-round device program (sync cells)
    # and the faulted resident commit program (the commit cell)
    ref_params, ref_metrics, *_ = run_cell("resident", "round")
    ref_commit_params, ref_commit_metrics, *_ = run_cell("resident",
                                                         "commit")
    report = {"rounds": rounds, "clients": C,
              "fault": {"client_drop_rate": 0.25,
                        "straggler_rate": 0.25,
                        "nan_inject_rate": 0.1, "guard": "reject"},
              "cells": {}}
    t0 = time.time()
    for source, dispatch, execution in cells:
        params, metrics, retraces, finite, wall = run_cell(source,
                                                           dispatch)
        rp, rm = (ref_commit_params, ref_commit_metrics) \
            if dispatch == "commit" else (ref_params, ref_metrics)
        # operands were already fetched to host above
        max_diff = max(
            float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
            for a, b in zip(jax.tree.leaves(params),
                            jax.tree.leaves(rp)))
        metric_diff = max(
            float(np.max(np.abs(np.asarray(a, np.float64)
                                - np.asarray(b, np.float64))))
            for a, b in zip(jax.tree.leaves(metrics),
                            jax.tree.leaves(rm)))
        name = cell_name(source, dispatch, execution)
        entry = {"retraces": retraces, "finite": finite,
                 "bitwise_vs_reference": max_diff == 0.0
                 and metric_diff == 0.0,
                 "max_abs_diff": max_diff, "wall_s": round(wall, 2)}
        report["cells"][name] = entry
        log(f"builder cell {name}: retraces={retraces} "
            f"bitwise={entry['bitwise_vs_reference']} "
            f"wall={wall:.2f}s")
        assert finite, f"{name}: non-finite params under chaos"
        assert retraces == 0, f"{name}: retraced {retraces}x mid-run"
        # the bitwise bar is an XLA-CPU guarantee (run_rounds
        # docstring: a scan body is a separate XLA compilation, which
        # other backends may reassociate at ulp level) — on-chip the
        # assertion hedges to ulp tolerance and the JSON records the
        # measured bitwise flag either way
        if jax.default_backend() == "cpu":
            assert entry["bitwise_vs_reference"], (
                f"{name}: trajectory diverged from its reference "
                f"program (max|d| params {max_diff}, metrics "
                f"{metric_diff})")
        else:
            assert max_diff <= 1e-5 and metric_diff <= 1e-4, (
                f"{name}: trajectory diverged beyond ulp tolerance "
                f"from its reference program (max|d| params "
                f"{max_diff}, metrics {metric_diff})")
    report["wall_seconds"] = round(time.time() - t0, 1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"builder matrix written to {out_path}")
    return report


# the full rule surface IS the matrix's aggregator axis — importing
# the stdlib-only config tuple keeps the two from drifting when a new
# rule lands ('mean' first = the negative control)
from fedtorch_tpu.config import ROBUST_AGGREGATORS as ATTACK_AGGREGATORS  # noqa: E402,E501

ATTACK_MODES = ("sign_flip", "collude", "gauss")


def run_attack_matrix(rounds: int = 20, smoke: bool = False,
                      tol_points: float = 5.0, seed: int = 0,
                      algorithm: str = "fedavg",
                      modes=None, aggregators=None,
                      byzantine_rate: float = 0.25,
                      byzantine_scale: float = 3.0,
                      out_path: str = None) -> dict:
    """The byzantine attack x robust-aggregator matrix (ISSUE 9).

    Every armed cell keeps the update GUARDS ON — the point of the
    byzantine threat model is that these attacks pass the benign-fault
    screen (a sign-flipped delta at scale 3 sits at 3x the median norm,
    under the 10x guard threshold), so the robust rule is the only
    defense actually being exercised. ``robust_trim_frac`` is set to
    the armed byzantine rate + margin: trimming/krum must budget for at
    least the adversarial fraction they face.

    Acceptance (the sign_flip row): plain ``mean`` must lose MORE than
    ``tol_points`` accuracy vs fault-free (the attack bites) while at
    least one robust aggregator stays within ``tol_points``.

    DATA: an IID partition of one pooled task mixture — NOT the
    per-client LEAF generator the fault suite uses. The LEAF-style
    generator draws each client's own feature means and label model at
    unit scale even at alpha=beta=0, so its clients are intrinsically
    heterogeneous (measured: honest full-batch client updates have
    cos ~0.35 to their mean), and coordinate-median/krum are BIASED
    estimators under heterogeneity with zero adversaries present
    (median plateaued 11 pts below mean on it, attack-free). The
    robust-aggregation literature states its guarantees under bounded
    heterogeneity; pooling ``C`` generator tasks and partitioning the
    shuffled pool IID isolates the axis this matrix actually measures
    — byzantine corruption — while the mixture keeps the task
    non-trivial.
    """
    import jax
    import numpy as np

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.config import (
        DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
        ModelConfig, OptimConfig, TrainConfig,
    )
    from fedtorch_tpu.data.batching import stack_partitions
    from fedtorch_tpu.data.synthetic import generate_synthetic
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer, evaluate
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    modes = tuple(modes) if modes else (
        ("sign_flip",) if smoke else ATTACK_MODES)
    aggregators = tuple(aggregators) if aggregators else (
        ("mean", "median", "krum") if smoke else ATTACK_AGGREGATORS)
    C = 8 if smoke else 16
    B = 32 if smoke else 64
    K = 2
    rounds = max(rounds, 8)

    # IID pool: C generator tasks concatenated, shuffled, split evenly
    syn = generate_synthetic(num_tasks=C, alpha=0.0, beta=0.0,
                             num_dim=30, num_classes=2)
    x = np.concatenate(syn.client_x)
    y = np.concatenate(syn.client_y)
    perm = np.random.RandomState(seed).permutation(len(x))
    x, y = x[perm], y[perm]
    n = (len(x) // C) * C
    parts = [np.arange(i * (n // C), (i + 1) * (n // C))
             for i in range(C)]
    data = stack_partitions(x[:n], y[:n], parts)

    def one_run(fault: FaultConfig):
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=30,
                            batch_size=B),
            federated=FederatedConfig(
                federated=True, num_clients=C, num_comms=rounds,
                online_client_rate=1.0, algorithm=algorithm,
                sync_type="local_step"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=K),
            fault=fault,
        ).finalize()
        model = define_model(cfg, batch_size=B)
        trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                                   data)
        server, clients = trainer.init_state(jax.random.key(seed))
        counters = {"byzantine": 0.0, "rejected": 0.0, "selected": 0.0,
                    "trimmed": 0.0, "retraces": 0}

        def count(m):
            # one batched fetch per round (lint FTL001)
            byz, rej, sel, trm = jax.device_get(
                (m.byzantine_clients, m.rejected_updates,
                 m.robust_selected, m.robust_trimmed))
            counters["byzantine"] += float(byz)
            counters["rejected"] += float(rej)
            counters["selected"] += float(sel)
            counters["trimmed"] += float(trm)

        # round 0 pays the (expected) trace but its faults still count
        server, clients, m = trainer.run_round(server, clients)
        count(m)
        with RecompilationSentinel() as sentinel:
            for _ in range(rounds - 1):
                server, clients, m = trainer.run_round(server, clients)
                count(m)
        counters["retraces"] = sum(sentinel.counts.values())
        # one transfer for the whole EvalResult pytree (lint FTL001)
        res = jax.device_get(evaluate(model, server.params, syn.test_x,
                                      syn.test_y))
        return float(res.top1), counters

    trim = min(byzantine_rate + 0.1, 0.45)
    clean_acc, _ = one_run(FaultConfig(guard_updates=True))
    report = {
        "algorithm": algorithm, "rounds": rounds, "clients": C,
        "tol_points": tol_points, "clean_top1": round(clean_acc, 4),
        "byzantine_rate": byzantine_rate,
        "byzantine_scale": byzantine_scale,
        "robust_trim_frac": trim, "guards": "on (10x median, reject)",
        "matrix": {},
    }
    t0 = time.time()
    for mode in modes:
        row = {}
        for agg in aggregators:
            fault = FaultConfig(
                byzantine_rate=byzantine_rate, byzantine_mode=mode,
                byzantine_scale=byzantine_scale, guard_updates=True,
                robust_agg=agg, robust_trim_frac=trim)
            acc, counters = one_run(fault)
            gap = (clean_acc - acc) * 100.0
            row[agg] = {
                "top1": round(acc, 4), "gap_points": round(gap, 2),
                "byzantine_injected": int(counters["byzantine"]),
                "guard_rejected": int(counters["rejected"]),
                "robust_trimmed": int(counters["trimmed"]),
                "retraces": counters["retraces"],
            }
            log(f"attack {mode} x {agg}: top1 {acc:.4f} "
                f"(gap {gap:+.2f}pts, "
                f"{int(counters['byzantine'])} byz injected, "
                f"{int(counters['rejected'])} guard-rejected, "
                f"{counters['retraces']} retraces)")
            assert counters["byzantine"] > 0, \
                f"{mode} x {agg}: attack schedule injected nothing"
            assert counters["retraces"] == 0, (
                f"{mode} x {agg}: robust aggregator retraced "
                f"{counters['retraces']}x mid-run (trace-once bar)")
        report["matrix"][mode] = row

    report["wall_seconds"] = round(time.time() - t0, 1)

    # the acceptance bar rides the sign_flip row when armed
    if "sign_flip" in report["matrix"] and "mean" in aggregators:
        row = report["matrix"]["sign_flip"]
        mean_gap = row["mean"]["gap_points"]
        robust_gaps = {a: c["gap_points"] for a, c in row.items()
                       if a != "mean"}
        best = min(robust_gaps, key=robust_gaps.get)
        report["acceptance"] = {
            "mean_gap_points": mean_gap,
            "best_robust": best,
            "best_robust_gap_points": robust_gaps[best],
            "attack_bites": mean_gap > tol_points,
            "defense_holds": robust_gaps[best] <= tol_points,
        }
        log(f"attack matrix: mean gap {mean_gap:+.2f}pts (must exceed "
            f"{tol_points}); best robust {best} "
            f"{robust_gaps[best]:+.2f}pts (must be within)")
        assert mean_gap > tol_points, (
            f"negative control failed: 25% sign_flip cost plain mean "
            f"only {mean_gap:.2f}pts (<= {tol_points}) — the attack "
            "does not bite, so the matrix proves nothing")
        assert robust_gaps[best] <= tol_points, (
            f"no robust aggregator held: best ({best}) lost "
            f"{robust_gaps[best]:.2f}pts (> {tol_points})")

    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"attack matrix written to {out_path}")
    return report


def run_ledger_attack(rounds: int = 20, smoke: bool = False,
                      seed: int = 6, byzantine_rate: float = 0.25,
                      byzantine_scale: float = 3.0, aggregators=None,
                      min_precision: float = 0.66,
                      out_path: str = None) -> dict:
    """The ledger-separation drill (ISSUE 14): one REAL CLI run
    (``run_experiment`` — telemetry, the batched cohort fetch, the
    per-client ledger) per robust rule with the PR 9 persistent
    byzantine cohort armed and ``--cohort_stats`` on. Acceptance: the
    persisted ``client_ledger.json``'s cumulative-suspicion ranking
    must SEPARATE the true adversarial cohort from honest clients —
    precision/recall of the top-``n`` ranking (``n`` = cohort size)
    against the cohort mask recomputed from the seed (the cohort is a
    pure function of ``server.rng``, robustness/chaos.py). Writes
    ``COHORT_AB.json``.

    Guards stay ON in every cell (the PR 9 threat model: these attacks
    pass the benign-fault screen, so suspicion — not rejection — is
    the only record naming the adversaries)."""
    import tempfile

    import jax
    import numpy as np

    from fedtorch_tpu.cli import run_experiment
    from fedtorch_tpu.config import (
        CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig,
        FederatedConfig, ModelConfig, OptimConfig, TelemetryConfig,
        TrainConfig,
    )
    from fedtorch_tpu.robustness.chaos import (
        BYZ_COHORT_FOLD, byzantine_cohort_mask,
    )
    from fedtorch_tpu.telemetry.ledger import (
        read_client_ledger, suspicion_ranking,
    )
    from fedtorch_tpu.telemetry.schema import iter_jsonl

    aggregators = tuple(aggregators) if aggregators else (
        ("median",) if smoke else ("median", "krum", "trimmed_mean"))
    C = 8 if smoke else 12
    rounds = max(rounds, 6 if smoke else 8)
    trim = min(byzantine_rate + 0.1, 0.45)

    # the true cohort: byzantine_cohort_mask folds BYZ_COHORT_FOLD off
    # server.rng, and init_state sets server.rng = split(key(seed))[0]
    # — replay the same two steps (pure function of the seed)
    run_key = jax.random.split(jax.random.key(seed))[0]
    cohort = np.asarray(jax.device_get(byzantine_cohort_mask(
        jax.random.fold_in(run_key, BYZ_COHORT_FOLD), C,
        byzantine_rate)))
    true = set(np.nonzero(cohort)[0].tolist())
    n = len(true)
    assert n > 0, "byzantine_rate * C rounded to an empty cohort"

    report = {
        "clients": C, "rounds": rounds, "seed": seed,
        "byzantine_rate": byzantine_rate,
        "byzantine_scale": byzantine_scale, "robust_trim_frac": trim,
        "byzantine_mode": "sign_flip", "true_cohort": sorted(true),
        "min_precision": min_precision, "cells": {},
    }
    t0 = time.time()
    for agg in aggregators:
        run_dir = tempfile.mkdtemp(prefix=f"ledger_attack_{agg}_")
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=10,
                            batch_size=8),
            federated=FederatedConfig(
                federated=True, num_clients=C, num_comms=rounds,
                online_client_rate=1.0, algorithm="fedavg",
                sync_type="local_step"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.1, weight_decay=0.0),
            train=TrainConfig(local_step=2, manual_seed=seed,
                              eval_freq=rounds),
            checkpoint=CheckpointConfig(run_dir=run_dir, debug=False),
            telemetry=TelemetryConfig(cohort_stats=True),
            fault=FaultConfig(
                byzantine_rate=byzantine_rate,
                byzantine_mode="sign_flip",
                byzantine_scale=byzantine_scale, guard_updates=True,
                robust_agg=agg, robust_trim_frac=trim),
        ).finalize()
        run_experiment(cfg)

        rows = [r for r in iter_jsonl(
            os.path.join(run_dir, "metrics.jsonl")) if "schema" not in r]
        injected = sum(r.get("byzantine", 0.0) for r in rows)
        assert injected > 0, \
            f"{agg}: the attack schedule injected nothing"
        doc = read_client_ledger(run_dir)
        assert doc["rounds"] == rounds, \
            f"{agg}: ledger recorded {doc['rounds']}/{rounds} rounds"
        ranking = suspicion_ranking(doc)
        top = {cid for cid, _ in ranking[:n]}
        hits = len(top & true)
        precision = hits / n
        recall = hits / n  # |top| == |true| == n, so the two coincide
        by_client = dict(ranking)
        byz_mean = float(np.mean([by_client.get(c, 0.0)
                                  for c in sorted(true)]))
        honest = [c for c in range(C) if c not in true]
        honest_mean = float(np.mean([by_client.get(c, 0.0)
                                     for c in honest]))
        cell = {
            "precision": round(precision, 4),
            "recall": round(recall, 4),
            "byzantine_injected": int(injected),
            "top_ranking": [[int(c), round(float(s), 4)]
                            for c, s in ranking[:n]],
            "byz_suspicion_mean": round(byz_mean, 4),
            "honest_suspicion_mean": round(honest_mean, 4),
            "separation": round(byz_mean / max(honest_mean, 1e-9), 3),
        }
        report["cells"][agg] = cell
        log(f"ledger attack x {agg}: precision {precision:.2f} "
            f"recall {recall:.2f} separation x{cell['separation']} "
            f"({int(injected)} byz injected)")
        assert precision >= min_precision, (
            f"{agg}: suspicion ranking precision {precision:.2f} < "
            f"{min_precision} — the ledger does not separate the "
            "byzantine cohort")
    best = max(report["cells"].values(), key=lambda c: c["precision"])
    report["acceptance"] = {
        "best_precision": best["precision"],
        "all_cells_pass": True,
    }
    report["wall_seconds"] = round(time.time() - t0, 1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"ledger-attack report written to {out_path}")
    return report


# the host-fault matrix's seam axis IS the config tuple — a new seam
# landing without a drill cell fails here, not in production
from fedtorch_tpu.config import HOST_FAULT_SEAMS  # noqa: E402


def run_host_fault_matrix(rounds: int = 12, smoke: bool = False,
                          seed: int = 0, rate: float = 0.25,
                          seams=None, out_path: str = None) -> dict:
    """The host-plane chaos drill (ISSUE 10): for every seam in
    ``HOST_FAULT_SEAMS``, run the REAL CLI loop (``run_experiment`` —
    telemetry, health, checkpointing, the stream plane) with the
    seeded injector armed at that seam, and prove:

    * **run-survival** — the run completes every round where the
      pre-PR behavior was an abort (a producer gather error, an
      ENOSPC mid-checkpoint, a telemetry write failure);
    * **exact recovery** — the per-round server-param trajectory is
      BITWISE-identical to the fault-free baseline (the data path
      replays a deterministic index schedule, so recovery must be
      exact, not approximate); the checkpoint seams additionally
      prove resume-stitching: the newest durable checkpoint restores
      bitwise against the live final state;
    * **observability** — >= 1 retry/degraded counter landed on the
      metrics rows and the seam's events fired (``chaos.host_fault``
      plus ``host.recovered`` / ``ckpt.degraded`` /
      ``stream.producer_rebuilt`` where the seam implies them);
    * **trace discipline** — the round program traces exactly as often
      as the fault-free run (the sentinel sees no injection-driven
      retrace).

    One extra cell, ``stream.rebuild``, drives the gather seam at rate
    1.0 with a fire cap of ``host_retry_max + 1``: the producer's own
    retries exhaust, the thread DIES, the consumer reports it
    promptly with the seam named, and the trainer rebuilds the
    producer through the ``invalidate_stream`` resync — the
    run-recovers-instead-of-aborting bar.

    Injection is a pure hash of (seed, seam, check index), so the
    whole matrix is replayable; results land in HOST_CHAOS_AB.json.
    """
    import hashlib
    import tempfile

    import jax
    import numpy as np

    from fedtorch_tpu.cli import run_experiment
    from fedtorch_tpu.config import (
        CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig,
        FederatedConfig, ModelConfig, OptimConfig, TelemetryConfig,
        TrainConfig,
    )
    from fedtorch_tpu.telemetry import iter_jsonl
    from fedtorch_tpu.utils.lock_sentinel import LockOrderSentinel
    from fedtorch_tpu.utils.tracing import RecompilationSentinel

    seams = tuple(seams) if seams else HOST_FAULT_SEAMS + (
        "stream.rebuild",)
    C = 6 if smoke else 10
    B = 8 if smoke else 16
    K = 2
    rounds = max(rounds, 6)
    root = tempfile.mkdtemp(prefix="host_chaos_")

    def cell_cfg(run_dir: str, fault: FaultConfig,
                 save_all: bool = False) -> ExperimentConfig:
        return ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=20,
                            batch_size=B, data_plane="stream"),
            federated=FederatedConfig(
                federated=True, num_clients=C, num_comms=rounds,
                online_client_rate=0.5, algorithm="fedavg",
                sync_type="local_step"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            # eval (and therefore a checkpoint write) every round: the
            # ckpt seams need real write traffic to bite
            train=TrainConfig(local_step=K, eval_freq=1),
            # save_all (the torn cell): per-round keeps give the
            # torn-main-checkpoint resume fallback something to stitch
            # from
            checkpoint=CheckpointConfig(run_dir=run_dir,
                                        async_save=True,
                                        save_all_models=save_all),
            telemetry=TelemetryConfig(level="default"),
            fault=fault,
        ).finalize()

    def fingerprint(leaves) -> str:
        h = hashlib.sha256()
        for leaf in leaves:
            h.update(np.ascontiguousarray(leaf).tobytes())
        return h.hexdigest()

    def one_run(name: str, fault: FaultConfig, save_all: bool = False):
        """One CLI run; returns (per-round param fingerprints,
        results, run_dir, trace count)."""
        run_dir = os.path.join(root, name.replace(".", "_"))
        fingerprints = []

        def cb(r, trainer, server, clients, metrics):
            fingerprints.append(fingerprint(
                jax.device_get(jax.tree.leaves(server.params))))

        cfg = cell_cfg(run_dir, fault, save_all)
        # the lock-order sentinel rides every drill cell: injected
        # faults exercise the writer/injector/recovery lock paths
        # under contention, exactly where an ordering inversion or a
        # re-entrant emit (the PR 10 self-deadlock) would surface
        with RecompilationSentinel() as sentinel, \
                LockOrderSentinel() as locks:
            results = run_experiment(cfg, round_callback=cb)
        return (fingerprints, results, run_dir, dict(sentinel.counts),
                locks.order_edges())

    def read_rows(run_dir):
        path = os.path.join(run_dir, "metrics.jsonl")
        if not os.path.exists(path):
            return []
        return [r for r in iter_jsonl(path) if "round" in r]

    def read_events(run_dir):
        path = os.path.join(run_dir, "events.jsonl")
        if not os.path.exists(path):
            return []
        return [r for r in iter_jsonl(path) if "event" in r]

    log(f"host-fault matrix: baseline ({rounds} rounds, C={C})")
    base_fps, base_res, base_dir, base_traces, base_lock_edges = \
        one_run("baseline", FaultConfig())
    assert len(base_fps) == rounds, "baseline did not complete"

    report = {"rounds": rounds, "clients": C, "rate": rate,
              "seed": seed, "baseline_traces": base_traces,
              "baseline_lock_order": base_lock_edges,
              "lock_order_violations": 0,
              "matrix": {}}
    t0 = time.time()
    for seam in seams:
        if seam == "stream.rebuild":
            # rate 1.0 + a fire cap of retries+1: the producer's own
            # gather retries exhaust exactly once, the thread dies,
            # and the trainer must rebuild it
            retry_max = FaultConfig().host_retry_max
            fault = FaultConfig(host_fault_seams="stream.gather",
                                host_fault_rate=1.0,
                                host_fault_seed=seed,
                                host_fault_max=retry_max + 1,
                                host_retry_backoff_s=0.0)
        else:
            fault = FaultConfig(host_fault_seams=seam,
                                host_fault_rate=rate,
                                host_fault_seed=seed,
                                host_retry_backoff_s=0.0)
        fps, results, run_dir, traces, lock_edges = one_run(
            seam, fault, save_all=seam == "ckpt.torn")

        # run-survival + bitwise trajectory (the stream plane replays
        # a deterministic schedule; recovery must be exact)
        assert len(fps) == rounds, \
            f"{seam}: faulted run aborted at round {len(fps)}"
        assert not results.get("preempted"), f"{seam}: run preempted"
        assert fps == base_fps, (
            f"{seam}: recovered trajectory diverged from the "
            "fault-free run (first mismatch at round "
            f"{[a == b for a, b in zip(base_fps, fps)].index(False)})")
        # trace-once with injection armed: the streamed round program
        # traced exactly once and NOTHING retraced (evaluate.run etc.
        # trace at most once per process — the baseline pays those)
        round_prog = "federated.round_stream[fedavg]"
        assert traces.get(round_prog) == 1, (
            f"{seam}: {round_prog} traced {traces.get(round_prog)}x "
            f"(trace-once bar); all counts: {traces}")
        assert all(v == 1 for v in traces.values()), (
            f"{seam}: a program retraced mid-run: {traces}")

        rows = read_rows(run_dir)
        events = read_events(run_dir)
        names = [e["event"] for e in events]
        last = rows[-1] if rows else {}
        fired = int(last.get("host_faults", 0))
        retries = int(last.get("host_retries", 0))
        recovered = int(last.get("host_recovered", 0))
        degraded = int(last.get("host_degraded", 0))
        rebuilds = int(last.get("stream_rebuilds", 0))
        entry = {
            "host_faults": fired, "host_retries": retries,
            "host_recovered": recovered, "host_degraded": degraded,
            "stream_rebuilds": rebuilds, "traces": traces,
            "lock_order": lock_edges,
            "bitwise_identical": True,
            "events": sorted(set(names) - {"run.start", "run.end"}),
        }

        # telemetry.write can degrade the metrics writer itself — the
        # injector fired even when the last row could not land; the
        # run dir's un-dropped rows/events still prove the drill
        if seam == "telemetry.write":
            assert fired >= 1 or "chaos.host_fault" in names or \
                degraded >= 1 or not rows, \
                f"{seam}: no observable injection"
        else:
            assert fired >= 1, f"{seam}: injector never fired " \
                f"(rows={bool(rows)})"
            assert "chaos.host_fault" in names, \
                f"{seam}: chaos.host_fault event missing"
        if seam in ("stream.gather", "stream.h2d", "ckpt.write"):
            assert retries >= 1, f"{seam}: no recovery retry counted"
            assert recovered >= 1 or degraded >= 1, \
                f"{seam}: neither recovered nor degraded"
            assert "host.recovered" in names \
                or "host.degraded" in names, \
                f"{seam}: no recovery/degrade event"
        if seam == "stream.rebuild":
            assert rebuilds >= 1, \
                "producer death did not trigger a rebuild"
            assert "stream.producer_rebuilt" in names, \
                "stream.producer_rebuilt event missing"
            rebuilt = [e for e in events
                       if e["event"] == "stream.producer_rebuilt"]
            assert any("stream.gather" in e.get("error", "")
                       for e in rebuilt), (
                "the rebuild event does not name the failing seam: "
                f"{rebuilt}")

        # checkpoint seams: resume-stitched identity — the newest
        # durable checkpoint (or, for the torn seam, the newest VALID
        # frame the resume fallback found) must restore BITWISE
        # against the live state it snapshotted at that round
        if seam in ("ckpt.write", "ckpt.torn"):
            entry["resume"] = _check_resume_stitch(
                cell_cfg(run_dir, fault), run_dir, fps, fingerprint,
                rounds, require_final=seam == "ckpt.write")
        report["matrix"][seam] = entry
        log(f"host-fault {seam}: faults={fired} retries={retries} "
            f"recovered={recovered} degraded={degraded} "
            f"rebuilds={rebuilds} bitwise=ok events={entry['events']}")

    report["wall_seconds"] = round(time.time() - t0, 1)
    if out_path:
        with open(out_path, "w") as f:
            json.dump(report, f, indent=1, sort_keys=True)
        log(f"host-fault matrix written to {out_path}")
    return report


def _check_resume_stitch(cfg, run_dir: str, fps, fingerprint,
                         rounds: int, require_final: bool):
    """Resume from the faulted run's directory into a fresh trainer:
    the restored params must BITWISE match the live trajectory at the
    restored round. ``require_final`` (the ENOSPC seam, where per-write
    retry absorbs every fault) additionally demands the FINAL round —
    the torn seam may legitimately stitch from an earlier round when
    the last ``checkpoint.ckpt`` write landed torn and the resume
    fallback picked the newest valid per-round keep."""
    import warnings as _warnings

    import jax

    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    from fedtorch_tpu.utils.checkpoint import maybe_resume

    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                               data.train)
    server, clients = trainer.init_state(
        jax.random.key(cfg.train.manual_seed))
    with _warnings.catch_warnings():
        # the torn seam's fallback warns by design
        _warnings.simplefilter("ignore", RuntimeWarning)
        server, clients, _, resumed = maybe_resume(
            run_dir, server, clients, cfg)
    assert resumed, "no durable checkpoint survived the ckpt drill"
    resumed_round = int(jax.device_get(server.round))
    assert 1 <= resumed_round <= rounds, resumed_round
    if require_final:
        assert resumed_round == rounds, \
            f"retried writes still lost rounds ({resumed_round})"
    restored_fp = fingerprint(
        jax.device_get(jax.tree.leaves(server.params)))
    assert restored_fp == fps[resumed_round - 1], (
        f"restored round {resumed_round} params do not match the live "
        "trajectory (resume-stitch not bitwise)")
    trainer.invalidate_stream()
    return {"resumed_round": resumed_round, "bitwise": True}


def run_kill_drill(rounds: int = 150, ckpt_root: str = None) -> dict:
    """Process-lifecycle chaos (ISSUE 4): SIGTERM the REAL CLI mid-run,
    assert it drains and exits 75, then let the ElasticRunner harness
    relaunch it with --resume and finish the job. The bitwise
    trajectory-identity half of this drill lives in
    tests/test_kill_drill.py; this entry checks the operator-facing
    lifecycle end to end (drain -> restartable exit -> relaunch ->
    completion) against the production entry point.

    The children are the chip-using processes: this parent must not
    have touched a backend before it launches them (a process that has
    holds the chip), so main() runs this drill BEFORE the in-process
    suite, and the children inherit the parent's platform choice."""
    import signal
    import subprocess
    import tempfile
    import threading

    from fedtorch_tpu.robustness.harness import (
        ElasticRunner, read_checkpoint_round,
    )

    run_dir = os.path.join(ckpt_root or tempfile.mkdtemp(), "run")
    cmd = [sys.executable, "-m", "fedtorch_tpu.cli",
           "--federated", "true", "-d", "synthetic", "-a",
           "logistic_regression", "--num_comms", str(rounds),
           "--num_workers", "8", "--online_client_rate", "0.5",
           "--federated_sync_type", "local_step", "--local_step", "2",
           "--batch_size", "8", "--lr", "0.1", "--eval_freq", "1",
           "--debug", "false", "--run_dir", run_dir]
    state = {"killed": False}

    def popen(c, **kw):
        proc = subprocess.Popen(c, stdout=subprocess.DEVNULL,
                                stderr=subprocess.DEVNULL)
        if not state["killed"]:
            # watch checkpoint.json; SIGTERM once the run is mid-flight
            def killer():
                while proc.poll() is None:
                    r = read_checkpoint_round(run_dir)
                    if r is not None and r >= 3:
                        state["killed"] = True
                        try:
                            proc.send_signal(signal.SIGTERM)
                        except OSError:  # raced to exit
                            pass
                        return
                    time.sleep(0.02)

            # daemon watcher scoped to the child process: it exits as
            # soon as proc.poll() turns non-None, so there is no close
            # path to join it from
            threading.Thread(target=killer, daemon=True,  # lint: disable=FTH005 — exits with the watched proc; nothing outlives popen
                             name="chaos-kill-watcher").start()
        return proc

    runner = ElasticRunner(cmd, ckpt_dir=run_dir, max_restarts=3,
                           backoff_base_s=0.1, popen=popen, log_fn=log)
    t0 = time.time()
    rc = runner.run()
    final_round = read_checkpoint_round(run_dir)
    assert state["killed"], \
        "kill drill never landed its SIGTERM (job finished too fast — " \
        "raise rounds)"
    assert rc == 0, f"relaunched job did not complete cleanly (rc={rc})"
    assert runner.launches >= 2, \
        "child was killed but the harness never relaunched it"
    assert final_round == rounds, \
        f"resumed job stopped at round {final_round}, wanted {rounds}"
    report = {"rounds": rounds, "launches": runner.launches,
              "final_round": final_round,
              "wall_seconds": round(time.time() - t0, 1)}
    log(f"kill drill: {report}")
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes for CI")
    ap.add_argument("--tol", type=float, default=5.0,
                    help="max accuracy-point gap vs the fault-free run")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--kill-drill", action="store_true",
                    help="also run the process-lifecycle kill drill "
                         "(SIGTERM -> exit 75 -> relaunch -> complete)")
    ap.add_argument("--straggler-heavy", action="store_true",
                    help="long-tail delay preset: compare SYNC vs "
                         "ASYNC (sync_mode='async') under the "
                         "straggler-heavy schedule instead of clean "
                         "vs chaos (the ISSUE 6 convergence bar)")
    ap.add_argument("--attack-matrix", action="store_true",
                    help="run the byzantine attack x robust-aggregator "
                         "grid instead of the fault suite (plain mean "
                         "as the negative control) and write "
                         "--attack-out")
    ap.add_argument("--attack-out", default="ATTACK_AB.json",
                    help="output path for the attack-matrix report")
    ap.add_argument("--host-fault-matrix", action="store_true",
                    help="run the host-plane fault drill instead: one "
                         "real CLI run per HOST_FAULT_SEAMS seam with "
                         "the seeded injector armed, asserting "
                         "run-survival, bitwise-identical recovery, "
                         "resume-stitched checkpoints, fired "
                         "retry/degraded counters+events and no "
                         "injection-driven retrace; writes --host-out "
                         "(docs/robustness.md 'Host plane')")
    ap.add_argument("--host-out", default="HOST_CHAOS_AB.json",
                    help="output path for the host-fault-matrix report")
    ap.add_argument("--host-rate", type=float, default=0.25,
                    help="per-check injection rate for the host-fault "
                         "matrix cells")
    ap.add_argument("--builder-matrix", action="store_true",
                    help="run the round-program-builder smoke instead: "
                         "three representative (source x dispatch x "
                         "execution) cells under chaos + guards — the "
                         "scanned device path, the scanned streamed "
                         "program and the feed-sourced async commit — "
                         "each trace-once and bitwise vs its reference "
                         "program; writes --builder-out")
    ap.add_argument("--builder-out", default="BUILDER_MATRIX.json",
                    help="output path for the builder-matrix report")
    ap.add_argument("--availability-matrix", action="store_true",
                    help="run the deployment-realism drill instead: "
                         "default-model draws bitwise vs the raw "
                         "legacy straggler chain, armed trace-model "
                         "lifecycle seeded-replayable + trace-once, "
                         "sub-quorum degrade-vs-abort, async "
                         "trace-model dropouts deterministic; writes "
                         "--avail-out (docs/robustness.md §7)")
    ap.add_argument("--avail-out", default="AVAIL_AB.json",
                    help="output path for the availability report")
    ap.add_argument("--ledger-attack", action="store_true",
                    help="run the ledger-separation drill instead: a "
                         "real CLI run per robust rule with the PR 9 "
                         "byzantine cohort + --cohort_stats on, "
                         "asserting the persisted client_ledger.json "
                         "suspicion ranking separates the adversarial "
                         "cohort from honest clients (precision/"
                         "recall); writes --ledger-out "
                         "(docs/observability.md 'Federation plane')")
    ap.add_argument("--ledger-out", default="COHORT_AB.json",
                    help="output path for the ledger-attack report")
    ap.add_argument("--privacy-matrix", action="store_true",
                    help="run the privacy-plane drill instead: DP-off "
                         "HLO byte-identity, the RDP accountant vs "
                         "closed-form epsilon, the measured "
                         "eps-vs-accuracy frontier (eps in {2,8,inf}, "
                         "noise calibrated against the accountant), "
                         "DP x trimmed_mean x byzantine layered leg, "
                         "and both budget-exhaustion drills through "
                         "the real CLI loop; writes --privacy-out "
                         "(docs/robustness.md §8)")
    ap.add_argument("--privacy-out", default="DP_AB.json",
                    help="output path for the privacy report")
    args = ap.parse_args()
    if args.privacy_matrix:
        report = run_privacy_matrix(rounds=args.rounds,
                                    smoke=args.smoke, seed=args.seed,
                                    out_path=args.privacy_out)
        log(json.dumps(report, indent=1, sort_keys=True))
        return
    if args.availability_matrix:
        report = run_availability_matrix(rounds=args.rounds,
                                         smoke=args.smoke,
                                         seed=args.seed,
                                         out_path=args.avail_out)
        print(json.dumps(report), flush=True)
        return
    if args.ledger_attack:
        report = run_ledger_attack(rounds=args.rounds,
                                   smoke=args.smoke, seed=args.seed,
                                   out_path=args.ledger_out)
        print(json.dumps(report), flush=True)
        return
    if args.builder_matrix:
        report = run_builder_matrix(rounds=args.rounds,
                                    smoke=args.smoke, seed=args.seed,
                                    out_path=args.builder_out)
        print(json.dumps(report), flush=True)
        return
    if args.host_fault_matrix:
        report = run_host_fault_matrix(rounds=args.rounds,
                                       smoke=args.smoke, seed=args.seed,
                                       rate=args.host_rate,
                                       out_path=args.host_out)
        print(json.dumps(report), flush=True)
        return
    if args.attack_matrix:
        report = run_attack_matrix(rounds=args.rounds, smoke=args.smoke,
                                   tol_points=args.tol, seed=args.seed,
                                   out_path=args.attack_out)
        print(json.dumps(report), flush=True)
        return
    # first, while this process has not touched a backend: the drill's
    # children need the chip
    kill_report = run_kill_drill(rounds=60 if args.smoke else 150) \
        if args.kill_drill else None
    report = run_suite(rounds=args.rounds, smoke=args.smoke,
                       tol_points=args.tol, seed=args.seed,
                       straggler_heavy=args.straggler_heavy)
    if kill_report is not None:
        report["kill_drill"] = kill_report
    print(json.dumps(report), flush=True)


if __name__ == "__main__":
    main()
