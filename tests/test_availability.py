"""Deployment-realism plane tests (docs/robustness.md "Deployment
realism"): the pluggable availability model behind both federation
planes, the sync round lifecycle (over-selection -> deadline ->
quorum), its health/supervisor escalation, and the deprecation of the
legacy straggler-knob aliasing.

The bars, per the engine-wide contracts:

* the ``default`` model reproduces the pre-availability scheduler
  draws BITWISE (recomputed here from the raw fold chain, independent
  of robustness/availability.py);
* every armed trajectory is a pure function of (seed, round/commit) —
  seeded replay is bitwise, fast-forward resume lands on the same
  event stream;
* the armed round program still traces exactly once per cell;
* sub-quorum rounds degrade (commit the renormalized partial cohort)
  instead of wedging, and 'abort' escalates into the supervisor's
  retry -> skip(cause='quorum') path.
"""
import os
import warnings

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    CheckpointConfig, DataConfig, ExperimentConfig, FaultConfig,
    FederatedConfig, ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.parallel import FederatedTrainer
from fedtorch_tpu.robustness import RoundSupervisor
from fedtorch_tpu.robustness.availability import (
    LEGACY_DELAY_SALT, DefaultAvailability, TraceAvailability,
    make_availability_model, synthesize_trace,
)
from fedtorch_tpu.async_plane.scheduler import (
    AsyncSchedule, simulate_sync_round_times,
)
from fedtorch_tpu.utils.tracing import RecompilationSentinel


def make_cfg(fault, *, num_clients=8, sync_mode="sync", plane="device",
             num_comms=6, run_dir=None, rate=0.5):
    ckpt = CheckpointConfig(run_dir=run_dir, debug=False) \
        if run_dir else CheckpointConfig()
    return ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=20,
                        batch_size=16, synthetic_alpha=0.5,
                        synthetic_beta=0.5, data_plane=plane),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients,
            num_comms=num_comms, online_client_rate=rate,
            algorithm="fedavg", sync_type="local_step",
            sync_mode=sync_mode),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.3, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        checkpoint=ckpt,
        fault=fault,
    ).finalize()


def make_trainer(fault, **kw):
    cfg = make_cfg(fault, **kw)
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    return FederatedTrainer(cfg, model, make_algorithm(cfg), data.train)


def fingerprint(tree):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(tree)]


def _key_state(seed):
    key = jax.random.key(seed)
    return (np.asarray(jax.random.key_data(key)),
            jax.random.key_impl(key))


def _sched(seed=0, *, num_clients=12, model=None, rate=0.4, frac=0.1,
           start_commit=0):
    kd, impl = _key_state(seed)
    return AsyncSchedule(kd, impl, num_clients=num_clients,
                         concurrency=4, buffer_size=2, ring_size=4,
                         straggler_rate=rate, straggler_step_frac=frac,
                         start_commit=start_commit, model=model)


def _commit_seq(sched, n):
    return [(cm.commit, cm.idx.tolist(), cm.version.tolist(),
             cm.dispatch.tolist(), cm.arrival_times.tolist())
            for cm in (sched.next_commit() for _ in range(n))]


# -- the default model: the legacy chain, bitwise ---------------------------
class TestDefaultModelBitwise:
    def test_first_dispatch_matches_raw_legacy_chain(self):
        """The scheduler's dispatch-0 delay equals the historical
        inline computation, recomputed here from the raw fold chain:
        u = uniform(fold(fold(key, SALT), did), (2,)), host-f64 tail
        math. A moved draw anywhere in the refactor breaks this."""
        rate, frac = 0.4, 0.1
        sched = _sched(rate=rate, frac=frac)
        d0 = next(t for t, did, *_ in sched._heap if did == 0)
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            k = jax.random.fold_in(jax.random.key(0),
                                   LEGACY_DELAY_SALT)
            u = np.asarray(jax.random.uniform(
                jax.random.fold_in(k, 0), (2,)), np.float64)
        base = 1.0 + 0.25 * u[1]
        want = base * (1.0 / frac) if u[0] < rate else base
        assert d0 == want

    def test_commit_sequence_replays_and_fast_forwards(self):
        seq = _commit_seq(_sched(), 6)
        assert _commit_seq(_sched(), 6) == seq
        # a fresh instance fast-forwarded to commit 3 replays the tail
        assert _commit_seq(_sched(start_commit=3), 3) == seq[3:]

    def test_arming_dropout_leaves_legacy_columns_untouched(self):
        """avail_dropout_rate adds an INDEPENDENT third draw column:
        the delay/straggler columns (and so every arrival time) are
        bitwise those of the dropout-free model."""
        kd, impl = _key_state(0)
        key = jax.random.wrap_key_data(jnp.asarray(kd), impl=impl)
        ids = np.arange(8, dtype=np.int32)
        clients = np.zeros(8, np.int32)
        plain = DefaultAvailability(straggler_rate=0.4,
                                    straggler_step_frac=0.1)
        armed = DefaultAvailability(straggler_rate=0.4,
                                    straggler_step_frac=0.1,
                                    dropout_rate=0.5)
        u_p = np.asarray(plain.traced(key, ids, clients, ids))
        u_a = np.asarray(armed.traced(key, ids, clients, ids))
        assert u_a.shape[1] == 3
        np.testing.assert_array_equal(u_p, u_a[:, :2])

    def test_sync_round_simulation_unchanged(self):
        """simulate_sync_round_times still draws the raw legacy chain:
        pinned against an inline recomputation of round 0."""
        kd, impl = _key_state(3)
        times = simulate_sync_round_times(
            kd, impl, rounds=4, k_online=5, straggler_rate=0.4,
            straggler_step_frac=0.1)
        with jax.default_device(jax.local_devices(backend="cpu")[0]):
            k = jax.random.fold_in(jax.random.key(3),
                                   LEGACY_DELAY_SALT)
            u = np.asarray([jax.random.uniform(
                jax.random.fold_in(k, d), (2,)) for d in range(5)],
                np.float64)
        base = 1.0 + 0.25 * u[:, 1]
        delays = np.where(u[:, 0] < 0.4, base * 10.0, base)
        assert times[0] == delays.max()

    def test_legacy_spelling_warns_on_async(self):
        with pytest.warns(FutureWarning, match="legacy straggler-knob"):
            make_cfg(FaultConfig(straggler_rate=0.4,
                                 straggler_step_frac=0.1),
                     num_clients=12, sync_mode="async")

    def test_trace_model_spelling_does_not_warn(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", FutureWarning)
            make_cfg(FaultConfig(avail_model="trace",
                                 avail_dropout_rate=0.2),
                     num_clients=12, sync_mode="async")


# -- the trace model on the async plane -------------------------------------
class TestAsyncTraceModel:
    def _model(self):
        return TraceAvailability(dropout_rate=0.3, diurnal_period=8)

    def test_determinism_fast_forward_and_dropout_redispatch(self):
        seq = _commit_seq(_sched(model=self._model()), 6)
        s2 = _sched(model=self._model())
        assert _commit_seq(s2, 6) == seq
        assert s2.stats.dropouts > 0  # arrivals discarded+re-dispatched
        assert _commit_seq(
            _sched(model=self._model(), start_commit=3), 3) == seq[3:]

    def test_synthetic_trace_matches_model_draws(self):
        """synthesize_trace materializes the same fleet the model
        derives in-jit: class multipliers in the DEVICE_CLASSES set,
        phases in [0,1), pure function of the key."""
        kd, impl = _key_state(0)
        t1 = synthesize_trace(kd, impl, num_clients=16)
        t2 = synthesize_trace(kd, impl, num_clients=16)
        np.testing.assert_array_equal(t1["speed_multiplier"],
                                      t2["speed_multiplier"])
        assert set(np.unique(t1["speed_multiplier"])) <= {1.0, 2.0, 4.0}
        assert ((t1["diurnal_phase"] >= 0)
                & (t1["diurnal_phase"] < 1)).all()

    def test_async_trainer_end_to_end_deterministic(self):
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer

        def run():
            cfg = make_cfg(FaultConfig(avail_model="trace",
                                       avail_dropout_rate=0.3,
                                       straggler_rate=0.4,
                                       straggler_step_frac=0.1),
                           num_clients=12, sync_mode="async",
                           num_comms=4)
            data = build_federated_data(cfg)
            model = define_model(cfg, batch_size=cfg.data.batch_size)
            t = AsyncFederatedTrainer(cfg, model, make_algorithm(cfg),
                                      data.train)
            server, clients = t.init_state(jax.random.key(0))
            for _ in range(4):
                server, clients, _ = t.run_round(server, clients)
            st = t.schedule_stats
            t.invalidate_stream()
            return fingerprint(server.params), st.dropouts

        fp1, drops1 = run()
        fp2, drops2 = run()
        assert fp1 == fp2
        assert drops1 == drops2 > 0


# -- the sync round lifecycle -----------------------------------------------
ARMED = dict(avail_model="trace", avail_dropout_rate=0.3,
             avail_diurnal_period=8, over_select_frac=1.5,
             avail_quorum_frac=0.5)


class TestSyncLifecycle:
    def test_counters_replay_and_trace_once(self):
        """The armed lifecycle composes with robust aggregation and
        guards: bitwise seeded replay, live counters riding the one
        batched fetch, the round program traced exactly once."""
        flt = FaultConfig(robust_agg="median", guard_updates=True,
                          **ARMED)

        def run():
            t = make_trainer(flt)
            server, clients = t.init_state(jax.random.key(0))
            totals = {"avail_dropped": 0.0, "deadline_missed": 0.0,
                      "quorum_degraded": 0.0}
            with RecompilationSentinel() as sentinel:
                for _ in range(4):
                    server, clients, m = t.run_round(server, clients)
                    for k in totals:
                        totals[k] += float(getattr(m, k))
            return (fingerprint(server.params), totals,
                    sum(sentinel.counts.values()))

        fp1, totals, traces = run()
        fp2, totals2, _ = run()
        assert fp1 == fp2 and totals == totals2
        assert traces == 1
        assert totals["avail_dropped"] + totals["deadline_missed"] > 0
        assert all(np.isfinite(np.frombuffer(b, np.float32)).all()
                   for b in fp1)

    def test_over_selection_widens_dispatch_not_acceptance(self):
        t = make_trainer(FaultConfig(**ARMED))
        assert t.k_dispatch == int(np.ceil(1.5 * t.k_online))
        server, clients = t.init_state(jax.random.key(0))
        _, _, m = t.run_round(server, clients)
        # at most k_online arrivals are accepted into aggregation
        assert float(m.online_mask.sum()) <= t.k_online

    @pytest.mark.parametrize("plane,dispatch", [
        ("device", "round"), ("stream", "round"), ("device", "scan"),
    ])
    def test_armed_cells_trace_once_and_replay(self, plane, dispatch):
        """The lifecycle is part of _round_core, so every legal sync
        builder cell carries it: per-cell trace-once + seeded
        replay."""
        flt = FaultConfig(robust_agg="trimmed_mean", **ARMED)

        def run():
            t = make_trainer(flt, plane=plane)
            server, clients = t.init_state(jax.random.key(0))
            with RecompilationSentinel() as sentinel:
                if dispatch == "scan":
                    for _ in range(2):
                        server, clients, _ = t.run_rounds(
                            server, clients, 2)
                else:
                    for _ in range(4):
                        server, clients, _ = t.run_round(
                            server, clients)
            t.invalidate_stream()
            return fingerprint(server.params), \
                sum(sentinel.counts.values())

        fp1, traces = run()
        fp2, _ = run()
        assert traces == 1
        assert fp1 == fp2

    def test_all_dropped_round_degrades_and_holds_server(self):
        """100% dropout: the accept mask is empty, renormalization
        holds the server (no NaN from a 0/0), the round still commits
        (counter advances) and is counted sub-quorum — the wedge case
        a naive deadline abort turns into a stall."""
        flt = FaultConfig(avail_dropout_rate=1.0, over_select_frac=1.5,
                          avail_quorum_frac=0.9)
        t = make_trainer(flt)
        server, clients = t.init_state(jax.random.key(0))
        p0 = fingerprint(server.params)
        server, clients, m = t.run_round(server, clients)
        assert fingerprint(server.params) == p0
        assert int(server.round) == 1
        assert float(m.quorum_degraded) == 1.0
        assert float(m.avail_dropped) == t.k_dispatch
        assert float(m.online_mask.sum()) == 0.0

    def test_disarmed_counters_stay_zero(self):
        t = make_trainer(FaultConfig())
        server, clients = t.init_state(jax.random.key(0))
        _, _, m = t.run_round(server, clients)
        assert float(m.avail_dropped) == 0.0
        assert float(m.deadline_missed) == 0.0
        assert float(m.quorum_degraded) == 0.0


# -- escalation: supervisor cause split + health intent ---------------------
class TestEscalation:
    def test_quorum_abort_skips_with_cause(self):
        causes = []
        flt = FaultConfig(supervisor=True, max_retries=1,
                          backoff_base_s=0.0,
                          avail_dropout_rate=1.0, over_select_frac=1.5,
                          avail_quorum_frac=0.9,
                          avail_quorum_action="abort")
        t = make_trainer(flt)
        sup = RoundSupervisor(t, sleep_fn=lambda s: None,
                              on_round_skipped=lambda r, c:
                              causes.append((r, c)))
        server, clients = t.init_state(jax.random.key(0))
        server, clients, _ = sup.run_round(server, clients)
        assert sup.stats.skipped_quorum == 1
        assert sup.stats.skipped_fault == 0
        assert sup.stats.retries == 1  # reseeded redraw was attempted
        assert causes == [(0, "quorum")]
        assert int(server.round) == 1  # skip advances, never wedges

    def test_fault_skip_keeps_cause_fault(self):
        causes = []
        flt = FaultConfig(nan_inject_rate=1.0, max_retries=0,
                          backoff_base_s=0.0)
        t = make_trainer(flt)
        sup = RoundSupervisor(t, sleep_fn=lambda s: None,
                              on_round_skipped=lambda r, c:
                              causes.append(c))
        server, clients = t.init_state(jax.random.key(0))
        sup.run_round(server, clients)
        assert sup.stats.skipped_fault == 1
        assert sup.stats.skipped_quorum == 0
        assert causes == ["fault"]

    def test_degrade_action_never_enters_supervisor_skip(self):
        flt = FaultConfig(supervisor=True, max_retries=1,
                          backoff_base_s=0.0,
                          avail_dropout_rate=1.0, over_select_frac=1.5,
                          avail_quorum_frac=0.9)  # action: degrade
        t = make_trainer(flt)
        sup = RoundSupervisor(t, sleep_fn=lambda s: None)
        server, clients = t.init_state(jax.random.key(0))
        for _ in range(2):
            server, clients, _ = sup.run_round(server, clients)
        assert sup.stats.skipped_rounds == 0
        assert sup.stats.healthy_rounds == 2

    def test_persistent_subquorum_writes_degraded_intent(self, tmp_path):
        from fedtorch_tpu.cli import run_experiment
        from fedtorch_tpu.telemetry import read_health
        run_dir = str(tmp_path / "avail_run")
        flt = FaultConfig(avail_dropout_rate=1.0, over_select_frac=1.5,
                          avail_quorum_frac=0.9)
        cfg = make_cfg(flt, num_comms=4, run_dir=run_dir)
        run_experiment(cfg)
        doc = read_health(run_dir)
        assert doc["intent"] == "degraded"


# -- config validation ------------------------------------------------------
class TestConfigValidation:
    def test_abort_requires_supervisor(self):
        with pytest.raises(ValueError, match="supervisor"):
            make_cfg(FaultConfig(avail_quorum_frac=0.5,
                                 avail_quorum_action="abort"))

    def test_unknown_model_rejected(self):
        with pytest.raises(ValueError, match="avail_model"):
            make_cfg(FaultConfig(avail_model="fedscale_live"))

    def test_quorum_frac_range_enforced(self):
        with pytest.raises(ValueError, match="avail_quorum_frac"):
            make_cfg(FaultConfig(avail_quorum_frac=1.5))

    def test_factory_picks_model_from_config(self):
        assert isinstance(
            make_availability_model(FaultConfig(avail_model="trace")),
            TraceAvailability)
        assert isinstance(
            make_availability_model(FaultConfig()),
            DefaultAvailability)
