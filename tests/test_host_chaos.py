"""Host-plane chaos + self-healing (docs/robustness.md "Host plane").

Covers the tentpole pair ``robustness/host_chaos.py`` (deterministic
seeded injector over the named host seams) and
``robustness/host_recovery.py`` (bounded retry, degraded modes, the
run-scoped ledger), plus the seam wiring: prompt producer-death
reporting (``HostPrefetcher``), producer rebuild through the
``invalidate_stream`` resync, checkpoint write retry + the
``AsyncCheckpointer`` degraded-to-sync fallback, telemetry writer
degrade-to-off, the supervisor's per-seam failure hook, and the CLI
surface.
"""
import os
import time

import jax
import numpy as np
import pytest

from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.robustness import host_chaos, host_recovery


@pytest.fixture(autouse=True)
def _clean_hooks():
    """No installed injector/ledger may leak across tests."""
    yield
    host_chaos.HostFaultInjector((), rate=0.0).uninstall()
    host_recovery.HostRecovery().uninstall()


def _ledger():
    return host_recovery.HostRecovery(sleep_fn=lambda s: None).install()


# -- the injector ------------------------------------------------------------
class TestInjector:
    def test_fire_pattern_is_seed_deterministic(self):
        a = host_chaos.HostFaultInjector(("ckpt.write",), rate=0.5,
                                         seed=3)
        b = host_chaos.HostFaultInjector(("ckpt.write",), rate=0.5,
                                         seed=3)
        pa = [a.fire("ckpt.write") for _ in range(64)]
        pb = [b.fire("ckpt.write") for _ in range(64)]
        assert pa == pb
        assert any(pa) and not all(pa)
        c = host_chaos.HostFaultInjector(("ckpt.write",), rate=0.5,
                                         seed=4)
        assert [c.fire("ckpt.write") for _ in range(64)] != pa

    def test_rate_edges(self):
        never = host_chaos.HostFaultInjector(("ckpt.write",), rate=0.0)
        always = host_chaos.HostFaultInjector(("ckpt.write",), rate=1.0)
        assert not any(never.fire("ckpt.write") for _ in range(32))
        assert all(always.fire("ckpt.write") for _ in range(32))

    def test_seams_are_independent_streams(self):
        inj = host_chaos.HostFaultInjector(
            ("ckpt.write", "stream.gather"), rate=0.5, seed=0)
        pa = [inj.fire("ckpt.write") for _ in range(64)]
        pb = [inj.fire("stream.gather") for _ in range(64)]
        assert pa != pb  # distinct hash streams per seam

    def test_max_fires_caps_per_seam(self):
        inj = host_chaos.HostFaultInjector(("ckpt.write",), rate=1.0,
                                           max_fires=3)
        fired = sum(inj.fire("ckpt.write") for _ in range(20))
        assert fired == 3
        assert inj.total_fires() == 3
        assert inj.stats() == {"host_faults": 3.0}

    def test_unarmed_seam_and_unknown_seam(self):
        inj = host_chaos.HostFaultInjector(("ckpt.write",), rate=1.0)
        assert not inj.fire("stream.gather")  # armed subset only
        with pytest.raises(ValueError, match="unknown host-fault seam"):
            host_chaos.HostFaultInjector(("nope",))

    def test_module_helpers_noop_without_install(self):
        assert host_chaos.get_active() is None
        host_chaos.maybe_raise("stream.gather")  # no raise
        host_chaos.maybe_raise_io("ckpt.write")
        assert host_chaos.torn_length("ckpt.torn", 4) == 4

    def test_installed_helpers_raise_the_real_classes(self):
        inj = host_chaos.HostFaultInjector(
            ("stream.gather", "ckpt.write", "ckpt.torn"),
            rate=1.0).install()
        try:
            with pytest.raises(RuntimeError, match="stream.gather"):
                host_chaos.maybe_raise("stream.gather")
            with pytest.raises(OSError) as ei:
                host_chaos.maybe_raise_io("ckpt.write")
            import errno
            assert ei.value.errno == errno.ENOSPC
            assert host_chaos.torn_length("ckpt.torn", 100) == 50
        finally:
            inj.uninstall()

    def test_from_config_builds_only_when_armed(self):
        assert host_chaos.HostFaultInjector.from_config(
            FaultConfig()) is None
        inj = host_chaos.HostFaultInjector.from_config(FaultConfig(
            host_fault_seams="stream.gather,ckpt.write",
            host_fault_rate=0.5, host_fault_seed=9, host_fault_max=2))
        assert inj.seams == {"stream.gather", "ckpt.write"}
        assert inj.rate == 0.5 and inj.seed == 9 and inj.max_fires == 2


# -- the recovery layer ------------------------------------------------------
class TestRecovery:
    def test_retry_recovers_and_counts(self):
        rec = _ledger()
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 3:
                raise OSError("transient")
            return "ok"

        assert host_recovery.retry_io(flaky, "ckpt.write") == "ok"
        assert rec.retries["ckpt.write"] == 2
        assert rec.recovered["ckpt.write"] == 1
        assert rec.stats()["host_retries"] == 2.0

    def test_exhaustion_names_the_seam(self):
        _ledger()

        def broken():
            raise OSError("persistent")

        with pytest.raises(host_recovery.HostSeamError) as ei:
            host_recovery.retry_io(broken, "ckpt.write")
        assert ei.value.seam == "ckpt.write"
        assert "ckpt.write" in str(ei.value)
        assert isinstance(ei.value.__cause__, OSError)

    def test_backoff_doubles_and_caps(self):
        sleeps = []
        rec = host_recovery.HostRecovery(
            policy=host_recovery.RetryPolicy(max_retries=4,
                                             backoff_base_s=0.5,
                                             backoff_max_s=1.0),
            sleep_fn=sleeps.append).install()
        with pytest.raises(host_recovery.HostSeamError):
            host_recovery.retry(lambda: 1 / 0, "stream.gather",
                                retryable=(ZeroDivisionError,))
        assert sleeps == [0.5, 1.0, 1.0, 1.0]
        assert rec.retries["stream.gather"] == 4

    def test_non_retryable_class_propagates(self):
        _ledger()
        with pytest.raises(ValueError):
            host_recovery.retry_io(
                lambda: (_ for _ in ()).throw(ValueError("not io")),
                "ckpt.write")

    def test_degraded_is_idempotent_per_seam(self):
        rec = _ledger()
        rec.note_degraded("telemetry.write")
        rec.note_degraded("telemetry.write")
        assert rec.stats()["host_degraded"] == 1.0

    def test_default_ledger_backs_uninstalled_callers(self):
        # never installed: retry still works and counts SOMEWHERE
        before = host_recovery.get_active().stats()["host_retries"]
        calls = {"n": 0}

        def flaky():
            calls["n"] += 1
            if calls["n"] < 2:
                raise OSError("x")
            return 1

        host_recovery.get_active().sleep_fn = lambda s: None
        assert host_recovery.retry_io(flaky, "ckpt.write") == 1
        after = host_recovery.get_active().stats()["host_retries"]
        assert after == before + 1


# -- prefetcher liveness (satellite: prompt producer-death reporting) --------
class TestPrefetcherLiveness:
    def test_dead_producer_raises_promptly_not_after_timeout(self):
        from fedtorch_tpu.native.host_pipeline import HostPrefetcher

        def produce(step):
            raise RuntimeError("gather exploded at seam stream.gather")

        pf = HostPrefetcher(produce, depth=2, name="t-producer")
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="stream.gather"):
            pf.next(timeout=30.0)
        # the queued exception delivers once; LATER calls must still
        # fail fast from the stored error, naming the producer — not
        # burn the full timeout on a generic queue.Empty
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="t-producer"):
            pf.next(timeout=30.0)
        assert time.monotonic() - t0 < 5.0
        assert not pf.alive()
        pf.close()

    def test_wedged_producer_times_out_named(self):
        import threading
        from fedtorch_tpu.native.host_pipeline import HostPrefetcher
        release = threading.Event()

        def produce(step):
            release.wait(30)  # wedged, not dead
            raise StopIteration

        pf = HostPrefetcher(produce, depth=2, name="wedged-producer")
        with pytest.raises(TimeoutError, match="wedged-producer"):
            pf.next(timeout=0.5)
        assert pf.alive()  # genuinely wedged: thread still there for
        # the watchdog stack dump to name
        release.set()
        pf.close()


# -- streaming producer seams ------------------------------------------------
def _stream_trainer(tmp_path, fault=None, rounds=4, seed=0):
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=16,
                        batch_size=8, data_plane="stream"),
        federated=FederatedConfig(federated=True, num_clients=6,
                                  num_comms=rounds,
                                  online_client_rate=0.5,
                                  algorithm="fedavg",
                                  sync_type="local_step"),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.5, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        fault=fault if fault is not None else FaultConfig(),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=8)
    trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                               data.train)
    server, clients = trainer.init_state(jax.random.key(seed))
    return trainer, server, clients


def _run_fingerprints(trainer, server, clients, rounds):
    fps = []
    for _ in range(rounds):
        server, clients, _ = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
        fps.append([np.asarray(x).tobytes() for x in
                    jax.device_get(jax.tree.leaves(server.params))])
    trainer.invalidate_stream()
    return fps


class TestStreamSeams:
    @pytest.mark.slow
    def test_injected_gather_fault_recovers_bitwise(self, tmp_path):
        rounds = 4
        _ledger()
        t0, s0, c0 = _stream_trainer(tmp_path, rounds=rounds)
        base = _run_fingerprints(t0, s0, c0, rounds)

        fault = FaultConfig(host_fault_seams="stream.gather",
                            host_fault_rate=0.5, host_fault_seed=1,
                            host_retry_backoff_s=0.0)
        inj = host_chaos.HostFaultInjector.from_config(fault).install()
        try:
            t1, s1, c1 = _stream_trainer(tmp_path, fault=fault,
                                         rounds=rounds)
            got = _run_fingerprints(t1, s1, c1, rounds)
        finally:
            inj.uninstall()
        assert inj.total_fires() >= 1
        assert got == base  # recovery is exact, not approximate

    @pytest.mark.slow

    def test_producer_death_rebuilds_and_stays_bitwise(self, tmp_path):
        rounds = 4
        _ledger()
        t0, s0, c0 = _stream_trainer(tmp_path, rounds=rounds)
        base = _run_fingerprints(t0, s0, c0, rounds)

        # rate 1.0 capped at retries+1: the producer's own retries
        # exhaust exactly once -> thread dies -> trainer must rebuild
        retry_max = FaultConfig().host_retry_max
        fault = FaultConfig(host_fault_seams="stream.gather",
                            host_fault_rate=1.0,
                            host_fault_max=retry_max + 1,
                            host_retry_backoff_s=0.0)
        inj = host_chaos.HostFaultInjector.from_config(fault).install()
        try:
            t1, s1, c1 = _stream_trainer(tmp_path, fault=fault,
                                         rounds=rounds)
            got = _run_fingerprints(t1, s1, c1, rounds)
        finally:
            inj.uninstall()
        assert t1._stream_rebuilds >= 1
        assert t1.telemetry_gauges()["stream_rebuilds"] >= 1.0
        assert got == base

    @pytest.mark.slow

    def test_rebuild_budget_exhaustion_names_the_seam(self, tmp_path):
        _ledger()
        fault = FaultConfig(host_fault_seams="stream.gather",
                            host_fault_rate=1.0,  # uncapped: every
                            host_retry_backoff_s=0.0)  # rebuild dies
        inj = host_chaos.HostFaultInjector.from_config(fault).install()
        try:
            t1, s1, c1 = _stream_trainer(tmp_path, fault=fault)
            with pytest.raises(host_recovery.HostSeamError) as ei:
                t1.run_round(s1, c1)
            assert ei.value.seam == "stream.producer"
            t1.invalidate_stream()
        finally:
            inj.uninstall()

    @pytest.mark.slow

    def test_desync_closes_producer_before_raising(self, tmp_path):
        t1, s1, c1 = _stream_trainer(tmp_path)
        s1, c1, _ = t1.run_round(s1, c1)
        jax.block_until_ready(s1.params)
        producer = t1._stream
        assert producer is not None
        # a consumer whose expectation moved out from under the
        # producer (rollback/resume without invalidate_stream) hits
        # the label mismatch; the producer must be closed BEFORE the
        # error propagates so the failed run leaks no daemon thread
        # holding feed buffers. (run_round's rebuild wrapper absorbs
        # desyncs by resync; the contract under test is the
        # producer-level close-then-raise.)
        producer._expected += 1
        with pytest.raises(RuntimeError, match="desynced"):
            producer.next_feed()
        deadline = time.monotonic() + 5.0
        while producer.alive() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert not producer.alive()
        t1.invalidate_stream()

    @pytest.mark.slow

    def test_supervisor_counts_host_seam_failures(self, tmp_path):
        from fedtorch_tpu.robustness import RoundSupervisor
        _ledger()
        seen = []
        fault = FaultConfig(host_fault_seams="stream.gather",
                            host_fault_rate=1.0,
                            host_retry_backoff_s=0.0,
                            max_retries=1, backoff_base_s=0.0)
        inj = host_chaos.HostFaultInjector.from_config(fault).install()
        try:
            t1, s1, c1 = _stream_trainer(tmp_path, fault=fault)
            sup = RoundSupervisor(
                t1, sleep_fn=lambda s: None,
                on_host_fault=lambda seam, n, e: seen.append((seam, n)))
            with pytest.raises(host_recovery.HostSeamError):
                sup.run_round(s1, c1)
            assert sup.stats.host_seam_failures["stream.producer"] >= 1
            assert seen and seen[0][0] == "stream.producer"
            t1.invalidate_stream()
        finally:
            inj.uninstall()


# -- checkpoint seams --------------------------------------------------------
class TestCheckpointSeams:
    def test_atomic_write_retries_through_enospc(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import _atomic_write
        rec = _ledger()
        # seeded pattern with fires but no retry-exhausting streak
        inj = host_chaos.HostFaultInjector(("ckpt.write",), rate=0.25,
                                           seed=1).install()
        try:
            path = str(tmp_path / "f.bin")
            for i in range(8):
                _atomic_write(path, b"payload-%d" % i)
            assert open(path, "rb").read() == b"payload-7"
            assert inj.total_fires() >= 1
            assert rec.stats()["host_retries"] >= 1
        finally:
            inj.uninstall()

    def test_torn_keep_gc_and_quick_check(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import (
            _frame_payload, collect_round_keeps, frame_quick_ok,
        )
        d = str(tmp_path)
        framed = _frame_payload(b"x" * 64)
        for n in (1, 2, 3):
            with open(os.path.join(d, f"checkpoint_r{n}.ckpt"),
                      "wb") as f:
                f.write(framed)
        # the NEWEST keep lands torn (injected short write)
        with open(os.path.join(d, "checkpoint_r4.ckpt"), "wb") as f:
            f.write(framed[:len(framed) // 2])
        # a sub-magic-length stub (severe tear) is torn too — it must
        # not pass as "legacy" and eat a retention slot
        with open(os.path.join(d, "checkpoint_r5.ckpt"), "wb") as f:
            f.write(b"xx")
        assert frame_quick_ok(os.path.join(d, "checkpoint_r3.ckpt"))
        assert not frame_quick_ok(os.path.join(d, "checkpoint_r4.ckpt"))
        assert not frame_quick_ok(os.path.join(d, "checkpoint_r5.ckpt"))
        removed = collect_round_keeps(d, 2)
        names = sorted(os.path.basename(p) for p in removed)
        # torn r4/r5 never count against the budget and are swept;
        # the newest VALID frames (r2, r3) survive
        assert names == ["checkpoint_r1.ckpt", "checkpoint_r4.ckpt",
                         "checkpoint_r5.ckpt"]
        assert os.path.exists(os.path.join(d, "checkpoint_r3.ckpt"))
        assert os.path.exists(os.path.join(d, "checkpoint_r2.ckpt"))

    def test_gc_skips_unreadable_probe_instead_of_deleting(
            self, tmp_path, monkeypatch):
        """A keep whose probe fails with a transient read error must be
        LEFT ALONE — neither retained-counted nor deleted (deleting on
        an NFS blip would destroy the very frame retention protects)."""
        import fedtorch_tpu.utils.checkpoint as ck
        d = str(tmp_path)
        framed = ck._frame_payload(b"x" * 64)
        for n in (1, 2, 3):
            with open(os.path.join(d, f"checkpoint_r{n}.ckpt"),
                      "wb") as f:
                f.write(framed)
        real = ck._frame_probe

        def probe(path):
            if path.endswith("checkpoint_r3.ckpt"):
                return None  # transient read failure
            return real(path)

        monkeypatch.setattr(ck, "_frame_probe", probe)
        removed = ck.collect_round_keeps(d, 1)
        assert [os.path.basename(p) for p in removed] == \
            ["checkpoint_r1.ckpt"]
        # unreadable r3 untouched; newest VERIFIED frame r2 retained
        assert os.path.exists(os.path.join(d, "checkpoint_r3.ckpt"))
        assert os.path.exists(os.path.join(d, "checkpoint_r2.ckpt"))

    def test_legacy_unframed_keep_counts_as_valid(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import (
            collect_round_keeps, frame_quick_ok,
        )
        d = str(tmp_path)
        for n in (1, 2):
            with open(os.path.join(d, f"checkpoint_r{n}.ckpt"),
                      "wb") as f:
                f.write(b"legacy-bytes-no-magic")
        assert frame_quick_ok(os.path.join(d, "checkpoint_r1.ckpt"))
        removed = collect_round_keeps(d, 1)
        assert [os.path.basename(p) for p in removed] == \
            ["checkpoint_r1.ckpt"]


# -- telemetry write seam ----------------------------------------------------
class TestTelemetrySeams:
    def test_jsonl_writer_retries_buffered_rows_then_degrades(
            self, tmp_path):
        from fedtorch_tpu.telemetry.metrics import JsonlWriter
        _ledger()
        degraded = []
        w = JsonlWriter(str(tmp_path / "m.jsonl"), "s/v1",
                        on_degrade=degraded.append)
        # fire EVERY write: 3 consecutive failures -> degraded-to-off
        inj = host_chaos.HostFaultInjector(("telemetry.write",),
                                           rate=1.0).install()
        try:
            for r in range(5):
                w.write({"round": r}, flush=True)
            assert w.degraded and degraded == [w]
            assert w.write_errors >= 3
        finally:
            inj.uninstall()
        # degraded: inert, no raise, rows counted as dropped
        w.write({"round": 99}, flush=True)
        assert w.dropped_rows >= 1
        w.close()

    def test_jsonl_transient_fault_loses_nothing(self, tmp_path):
        from fedtorch_tpu.telemetry.metrics import JsonlWriter
        from fedtorch_tpu.telemetry.schema import iter_jsonl
        _ledger()
        w = JsonlWriter(str(tmp_path / "m.jsonl"), "s/v1")
        # seeded to fire on scattered flushes (never 3 consecutive):
        # failed flushes must KEEP their rows and land them on the
        # next healthy flush
        inj = host_chaos.HostFaultInjector(("telemetry.write",),
                                           rate=0.25, seed=1).install()
        try:
            for r in range(20):
                w.write({"round": r}, flush=True)
        finally:
            inj.uninstall()
        w.close()
        rows = [x for x in iter_jsonl(str(tmp_path / "m.jsonl"))
                if "round" in x]
        assert [x["round"] for x in rows] == list(range(20))
        assert inj.total_fires() >= 1 and not w.degraded

    @pytest.mark.parametrize("rate", [1.0, 0.3])
    def test_first_fire_announce_inside_flush_does_not_deadlock(
            self, tmp_path, rate):
        """The injector's first fire at the telemetry.write seam emits
        a chaos.host_fault event — which re-enters the EVENTS writer
        from inside that writer's own flush. With IO under the buffer
        mutex this self-deadlocked (confirmed), and a seam check under
        the open-lock deadlocked the same way at sub-1.0 rates (the
        announce lands on a flush that proceeds to open the file); the
        flush must run the seam check with NO writer lock held.

        The lock-order sentinel rides the whole path: the writers' and
        injector's locks are created inside its scope, so a re-entrant
        acquire (the original hang) raises immediately instead of
        hanging, and any order inversion between the three writer
        locks fails the test at exit."""
        import threading
        from fedtorch_tpu.telemetry import Telemetry
        from fedtorch_tpu.utils.lock_sentinel import LockOrderSentinel
        _ledger()
        with LockOrderSentinel() as locks:
            tel = Telemetry(str(tmp_path), level="default").install()
            inj = host_chaos.HostFaultInjector(
                ("telemetry.write",), rate=rate, seed=1).install()
            done = threading.Event()

            def emit():
                # every event flushes; rate 1.0 makes the first
                # flush's check the announcing fire
                for _ in range(5):
                    tel.event("probe")
                done.set()

            t = threading.Thread(target=emit, daemon=True,
                                 name="chaos-emit-probe")
            t.start()
            try:
                assert done.wait(20.0), \
                    "telemetry event emission deadlocked under injection"
            finally:
                inj.uninstall()
                tel.close()
            assert inj.total_fires() >= 1
            locks.assert_clean()

    def test_health_degrades_to_off_after_consecutive_failures(
            self, tmp_path):
        from fedtorch_tpu.telemetry.health import HealthFile
        rec = _ledger()
        # min_interval_s=0: the round-update throttle must not eat the
        # consecutive write attempts this test injects into
        hf = HealthFile(str(tmp_path / "health.json"),
                        min_interval_s=0.0)
        inj = host_chaos.HostFaultInjector(("telemetry.write",),
                                           rate=1.0).install()
        try:
            for i in range(4):
                hf.update("running", round_idx=i,
                          staleness=None)
        finally:
            inj.uninstall()
        assert hf.degraded and hf.write_errors >= 3
        assert "telemetry.write" in rec.degraded
        # in-memory doc stays current even with disk off
        doc = hf.update("running", round_idx=99)
        assert doc["round"] == 99
        assert not os.path.exists(str(tmp_path / "health.json"))


# -- native.load seam --------------------------------------------------------
class TestNativeLoadSeam:
    def test_forced_numpy_fallback_is_bitwise(self):
        from fedtorch_tpu.native.host_pipeline import gather_rows
        src = np.arange(40, dtype=np.float32).reshape(10, 4)
        idx = np.array([3, 1, 7, 7], np.int32)
        want = gather_rows(src, idx)
        inj = host_chaos.HostFaultInjector(("native.load",),
                                           rate=1.0).install()
        try:
            got = gather_rows(src, idx)  # load "fails" -> numpy path
            assert inj.fires["native.load"] >= 1
        finally:
            inj.uninstall()
        np.testing.assert_array_equal(got, want)
        # the forced failure never poisons the cached handle
        from fedtorch_tpu.native import host_pipeline
        assert host_pipeline.load_library() is host_pipeline._lib


# -- health schema + CLI surface ---------------------------------------------
class TestSurface:
    def test_new_health_intents_validate(self):
        from fedtorch_tpu.telemetry.health import HealthFile
        from fedtorch_tpu.telemetry.schema import validate_health
        hf = HealthFile(os.devnull + ".ignore")
        for intent in ("recovering", "degraded"):
            doc = dict(hf.update(intent, round_idx=1))
            validate_health(doc)

    def test_host_gauges_are_cataloged(self):
        from fedtorch_tpu.telemetry.schema import (
            METRICS_OPTIONAL, validate_metrics_row,
        )
        for key in ("host_faults", "host_retries", "host_recovered",
                    "host_degraded", "stream_rebuilds",
                    "ckpt_degraded", "ckpt_lost_writes"):
            assert key in METRICS_OPTIONAL
        row = {"round": 0, "round_s": 0.1, "loss": 1.0, "acc": 0.5,
               "lr": 0.1, "n_online": 3.0, "comm_bytes": 10.0,
               "host_faults": 1.0, "host_retries": 2.0,
               "host_recovered": 1.0, "host_degraded": 0.0,
               "stream_rebuilds": 1.0}
        validate_metrics_row(row)

    def test_cli_flags_map_to_config(self):
        from fedtorch_tpu.cli import args_to_config, build_parser
        args = build_parser().parse_args([
            "--federated", "true", "-d", "synthetic",
            "--host_fault_seams", "stream.gather,ckpt.write",
            "--host_fault_rate", "0.4", "--host_fault_seed", "11",
            "--host_fault_delay_s", "0.5", "--host_fault_max", "6",
            "--host_retry_max", "5", "--host_retry_backoff_s", "0.2",
        ])
        cfg = args_to_config(args)
        flt = cfg.fault
        assert flt.host_fault_seam_tuple == ("stream.gather",
                                             "ckpt.write")
        assert flt.host_fault_rate == 0.4 and flt.host_fault_seed == 11
        assert flt.host_fault_delay_s == 0.5 and flt.host_fault_max == 6
        assert flt.host_retry_max == 5
        assert flt.host_retry_backoff_s == 0.2
        assert flt.host_chaos_enabled

    def test_config_rejects_bad_host_fault_values(self):
        for kw in ({"host_fault_seams": "bogus.seam"},
                   {"host_fault_rate": 1.5},
                   {"host_fault_delay_s": -1.0},
                   {"host_fault_max": -1},
                   {"host_retry_max": -1},
                   {"host_retry_backoff_s": -0.1}):
            with pytest.raises(ValueError):
                ExperimentConfig(fault=FaultConfig(**kw)).finalize()

    @pytest.mark.slow

    def test_cli_run_with_armed_drill_completes_and_reports(
            self, tmp_path):
        """End to end through the REAL CLI loop: an armed gather drill
        completes, the metrics rows carry the host gauges, events
        fired, and health lands 'complete'."""
        from fedtorch_tpu.cli import main
        from fedtorch_tpu.telemetry import read_health
        from fedtorch_tpu.telemetry.schema import iter_jsonl
        run_dir = str(tmp_path / "run")
        results = main([
            "--federated", "true", "--data", "synthetic",
            "--federated_type", "fedavg", "--num_comms", "4",
            "--num_workers", "6", "--online_client_rate", "0.5",
            "--federated_sync_type", "local_step", "--local_step", "2",
            "--arch", "logistic_regression", "--batch_size", "8",
            "--weight_decay", "0", "--data_plane", "stream",
            "--run_dir", run_dir, "--debug", "false",
            "--host_fault_seams", "stream.gather",
            "--host_fault_rate", "0.5", "--host_fault_seed", "1",
            "--host_retry_backoff_s", "0",
        ])
        assert "best_top1" in results
        assert results["host_recovery"]["host_faults"] >= 1
        rows = [r for r in iter_jsonl(os.path.join(run_dir,
                                                   "metrics.jsonl"))
                if "round" in r]
        assert rows and rows[-1]["host_faults"] >= 1
        assert rows[-1]["host_retries"] >= 1
        events = [e["event"] for e in
                  iter_jsonl(os.path.join(run_dir, "events.jsonl"))
                  if "event" in e]
        assert "chaos.host_fault" in events
        doc = read_health(run_dir)
        assert doc["intent"] == "complete"
        # the injector/ledger must not leak past the run
        assert host_chaos.get_active() is None


# -- resume fallback (torn main checkpoint -> newest valid keep) -------------
class TestResumeFallback:
    def _experiment(self, tmp_path):
        from fedtorch_tpu.algorithms import make_algorithm
        from fedtorch_tpu.data import build_federated_data
        from fedtorch_tpu.models import define_model
        from fedtorch_tpu.parallel import FederatedTrainer
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=16,
                            batch_size=8),
            federated=FederatedConfig(federated=True, num_clients=4,
                                      num_comms=4,
                                      online_client_rate=1.0,
                                      algorithm="fedavg",
                                      sync_type="local_step"),
            model=ModelConfig(arch="logistic_regression"),
            optim=OptimConfig(lr=0.5, weight_decay=0.0),
            train=TrainConfig(local_step=2),
        ).finalize()
        data = build_federated_data(cfg)
        model = define_model(cfg, batch_size=8)
        trainer = FederatedTrainer(cfg, model, make_algorithm(cfg),
                                   data.train)
        server, clients = trainer.init_state(jax.random.key(0))
        return cfg, trainer, server, clients

    @pytest.mark.slow

    def test_torn_main_checkpoint_falls_back_to_newest_valid_keep(
            self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import (
            maybe_resume, save_checkpoint,
        )
        d = str(tmp_path)
        cfg, trainer, server, clients = self._experiment(tmp_path)
        for _ in range(3):
            server, clients, _ = trainer.run_round(server, clients)
            jax.block_until_ready(server.params)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
        want = [np.asarray(x) for x in
                jax.device_get(jax.tree.leaves(server.params))]
        # tear the main checkpoint (short write that landed)
        main_path = os.path.join(d, "checkpoint.ckpt")
        blob = open(main_path, "rb").read()
        with open(main_path, "wb") as f:
            f.write(blob[:len(blob) // 2])
        s2, c2 = trainer.init_state(jax.random.key(0))
        with pytest.warns(RuntimeWarning, match="newest valid"):
            s3, c3, _, resumed = maybe_resume(d, s2, c2, cfg)
        assert resumed
        assert int(jax.device_get(s3.round)) == 3  # checkpoint_r3
        got = [np.asarray(x) for x in
               jax.device_get(jax.tree.leaves(s3.params))]
        for a, b in zip(got, want):
            np.testing.assert_array_equal(a, b)

    def test_torn_keep_is_skipped_for_older_valid_one(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import (
            maybe_resume, save_checkpoint,
        )
        d = str(tmp_path)
        cfg, trainer, server, clients = self._experiment(tmp_path)
        fps = []
        for _ in range(3):
            server, clients, _ = trainer.run_round(server, clients)
            jax.block_until_ready(server.params)
            save_checkpoint(d, server, clients, cfg, 0.0, False,
                            save_all=True)
            fps.append([np.asarray(x) for x in
                        jax.device_get(jax.tree.leaves(server.params))])
        # tear BOTH the main checkpoint and the newest keep: resume
        # must skip the torn r3 and stitch from r2
        for name in ("checkpoint.ckpt", "checkpoint_r3.ckpt"):
            p = os.path.join(d, name)
            blob = open(p, "rb").read()
            with open(p, "wb") as f:
                f.write(blob[: len(blob) // 2])
        s2, c2 = trainer.init_state(jax.random.key(0))
        with pytest.warns(RuntimeWarning, match="checkpoint_r2"):
            s3, c3, _, resumed = maybe_resume(d, s2, c2, cfg)
        assert resumed and int(jax.device_get(s3.round)) == 2
        got = [np.asarray(x) for x in
               jax.device_get(jax.tree.leaves(s3.params))]
        for a, b in zip(got, fps[1]):
            np.testing.assert_array_equal(a, b)

    def test_torn_meta_falls_back_to_model_best_json(self, tmp_path):
        from fedtorch_tpu.utils.checkpoint import (
            maybe_resume, save_checkpoint,
        )
        d = str(tmp_path)
        cfg, trainer, server, clients = self._experiment(tmp_path)
        server, clients, _ = trainer.run_round(server, clients)
        jax.block_until_ready(server.params)
        save_checkpoint(d, server, clients, cfg, 0.5, True)  # is_best
        with open(os.path.join(d, "checkpoint.json"), "w") as f:
            f.write('{"arguments": {trunc')
        s2, c2 = trainer.init_state(jax.random.key(0))
        with pytest.warns(RuntimeWarning, match="model_best.json"):
            s3, c3, best, resumed = maybe_resume(d, s2, c2, cfg)
        assert resumed and int(jax.device_get(s3.round)) == 1
        assert best == 0.5
