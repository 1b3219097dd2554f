"""Unified run telemetry (``fedtorch_tpu.telemetry``,
docs/observability.md): the contracts ISSUE 7 makes executable.

* schema round-trip — every row the loop emits validates against the
  v1 catalog, and the catalog rejects drift (uncataloged fields);
* the ``fedtorch-tpu report`` tool renders a recorded mini-run (and
  falls back to the legacy ``record0`` regex parse);
* telemetry is HOST-ONLY: with it enabled the round/commit program
  still traces exactly once, lowers to byte-identical HLO, and the
  trajectory is bitwise-identical to a telemetry-off run — across
  device/stream planes x sync/async modes;
* ``health.json`` is atomically replaced: a reader polling through
  the SIGTERM drain drill never observes a torn document, and the
  exit intent lands as 'preempted'.
"""
import json
import os
import signal
import threading
import warnings

import jax
import numpy as np
import pytest

from fedtorch_tpu import telemetry
from fedtorch_tpu.algorithms import make_algorithm
from fedtorch_tpu.config import (
    DataConfig, ExperimentConfig, FaultConfig, FederatedConfig,
    ModelConfig, OptimConfig, TrainConfig,
)
from fedtorch_tpu.data import build_federated_data
from fedtorch_tpu.models import define_model
from fedtorch_tpu.telemetry import (
    HealthFile, JsonlWriter, SpanRecorder, Telemetry, health_path,
    iter_jsonl, read_health, validate_health, validate_metrics_row,
)
from fedtorch_tpu.telemetry.schema import (
    HEALTH_SCHEMA, METRICS_SCHEMA,
)
from fedtorch_tpu.utils.tracing import RecompilationSentinel


def make_trainer(algorithm="fedavg", plane="device", sync_mode="sync",
                 num_clients=8):
    cfg = ExperimentConfig(
        data=DataConfig(dataset="synthetic", synthetic_dim=10,
                        batch_size=8, data_plane=plane),
        federated=FederatedConfig(
            federated=True, num_clients=num_clients, num_comms=6,
            online_client_rate=0.5, algorithm=algorithm,
            sync_type="local_step", sync_mode=sync_mode),
        model=ModelConfig(arch="logistic_regression"),
        optim=OptimConfig(lr=0.1, weight_decay=0.0),
        train=TrainConfig(local_step=2),
        fault=FaultConfig(),
    ).finalize()
    data = build_federated_data(cfg)
    model = define_model(cfg, batch_size=cfg.data.batch_size)
    if sync_mode == "async":
        from fedtorch_tpu.async_plane import AsyncFederatedTrainer
        cls = AsyncFederatedTrainer
    else:
        from fedtorch_tpu.parallel import FederatedTrainer
        cls = FederatedTrainer
    return cls(cfg, model, make_algorithm(cfg), data.train)


def run_rounds_collect(trainer, n, seed=0):
    """n rounds; returns the flattened param trajectory (host)."""
    server, clients = trainer.init_state(jax.random.key(seed))
    traj = []
    for _ in range(n):
        server, clients, m = trainer.run_round(server, clients)
        traj.append(np.concatenate([
            np.ravel(x) for x in jax.tree.leaves(
                jax.device_get(server.params))]))
    trainer.invalidate_stream()
    return traj


VALID_ROW = {"round": 0, "round_s": 0.25, "loss": 1.0, "acc": 0.5,
             "lr": 0.1, "n_online": 4.0, "comm_bytes": 1e6}


# -- schema round-trip -------------------------------------------------------
class TestMetricsSchema:
    def test_writer_roundtrip_header_and_rows(self, tmp_path):
        path = str(tmp_path / "metrics.jsonl")
        w = JsonlWriter(path, METRICS_SCHEMA, run_meta={"algorithm":
                                                        "fedavg"})
        for r in range(3):
            w.write(dict(VALID_ROW, round=r))
        w.close()
        recs = list(iter_jsonl(path))
        header, rows = recs[0], recs[1:]
        assert header["schema"] == METRICS_SCHEMA
        assert header["run"] == {"algorithm": "fedavg"}
        assert [r["round"] for r in rows] == [0, 1, 2]
        for r in rows:
            validate_metrics_row(r)

    def test_optional_gauges_validate(self):
        validate_metrics_row(dict(
            VALID_ROW, stream_depth=2.0, async_buffer=4.0,
            ckpt_queue_depth=0.0, sup_rollbacks=0.0, eval_s=0.1,
            test_top1=0.9, staleness=1.5))

    def test_missing_required_rejected(self):
        row = dict(VALID_ROW)
        del row["comm_bytes"]
        with pytest.raises(ValueError, match="comm_bytes"):
            validate_metrics_row(row)

    def test_uncataloged_field_rejected(self):
        # schema drift fails loudly: a new gauge must enter the
        # catalog (which docs/observability.md renders), not sneak in
        with pytest.raises(ValueError, match="uncataloged"):
            validate_metrics_row(dict(VALID_ROW, my_new_gauge=1.0))

    def test_bool_is_not_numeric(self):
        with pytest.raises(ValueError, match="round_s"):
            validate_metrics_row(dict(VALID_ROW, round_s=True))

    def test_torn_tail_skipped(self, tmp_path):
        # crash mid-append: every complete line parses, the torn last
        # line is skipped (not fatal) — the consumer contract
        path = str(tmp_path / "metrics.jsonl")
        w = JsonlWriter(path, METRICS_SCHEMA)
        w.write(VALID_ROW)
        w.close()
        with open(path, "a") as f:
            f.write('{"round": 1, "round_s"')  # torn
        recs = [r for r in iter_jsonl(path) if "schema" not in r]
        assert len(recs) == 1 and recs[0]["round"] == 0

    def test_writer_inert_on_unwritable_dir(self, tmp_path):
        # telemetry must degrade, never kill training. A plain file
        # where the run dir should be makes every open fail (root in
        # the test container ignores permission bits, so chmod can't
        # inject this)
        (tmp_path / "blocked").write_text("")
        w = JsonlWriter(str(tmp_path / "blocked" / "metrics.jsonl"),
                        METRICS_SCHEMA)
        for r in range(5):
            w.write(dict(VALID_ROW, round=r), flush=True)
        w.close()
        assert w.write_errors >= 1

    def test_concurrent_writers_hold_lock_order(self, tmp_path):
        # the three-lock discipline (_mutex buffer-only, _open_lock,
        # _io_lock) under real contention: N threads hammer write()
        # while the main thread flushes — the lock-order sentinel
        # wraps the writer's locks at construction and fails the test
        # on any order inversion or re-entrant acquire (the PR 10
        # self-deadlock shape), instead of hanging it
        from fedtorch_tpu.utils.lock_sentinel import LockOrderSentinel

        path = str(tmp_path / "metrics.jsonl")
        with LockOrderSentinel() as locks:
            w = JsonlWriter(path, METRICS_SCHEMA, flush_rows=4)

            def hammer(base):
                for r in range(20):
                    w.write(dict(VALID_ROW, round=base + r))

            threads = [threading.Thread(target=hammer, args=(i * 100,),
                                        name=f"hammer-{i}")
                       for i in range(4)]
            for t in threads:
                t.start()
            for _ in range(10):
                w.flush()
            for t in threads:
                t.join()
            w.close()
            locks.assert_clean()
        rows = [r for r in iter_jsonl(path) if "round" in r]
        assert len(rows) == 80 and w.write_errors == 0


# -- host spans --------------------------------------------------------------
class TestSpanRecorder:
    def test_chrome_trace_export(self, tmp_path):
        rec = SpanRecorder()
        with rec.span("round", round=3):
            with rec.span("inner"):
                pass
        rec.instant("marker", round=3)
        path = str(tmp_path / "trace.json")
        n = rec.export(path)
        assert n == 3
        doc = json.load(open(path))
        evs = doc["traceEvents"]
        by_name = {e["name"]: e for e in evs}
        # complete events with microsecond ts/dur + args
        assert by_name["round"]["ph"] == "X"
        assert by_name["round"]["args"] == {"round": 3}
        assert by_name["round"]["dur"] >= by_name["inner"]["dur"] >= 0
        assert by_name["marker"]["ph"] == "i"
        # thread-name metadata gives Perfetto its lane labels
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
        assert doc["otherData"]["dropped_spans"] == 0

    def test_annotate_hook_opens_with_every_span(self):
        """The hook the CLI fills with ``jax.profiler.TraceAnnotation``
        (this package imports no jax): called with the span's name and
        args, entered before and left after the span is recorded."""
        import contextlib
        log = []

        @contextlib.contextmanager
        def annotate(name, **args):
            log.append(("open", name, args))
            yield
            log.append(("close", name, args))

        rec = SpanRecorder(annotate=annotate)
        with rec.span("round", round=3):
            with rec.span("round.wait"):
                pass
        assert log == [("open", "round", {"round": 3}),
                       ("open", "round.wait", {}),
                       ("close", "round.wait", {}),
                       ("close", "round", {"round": 3})]
        assert [e["name"] for e in rec.to_trace_events()
                if e["ph"] == "X"] == ["round.wait", "round"]

    def test_compile_listener_sees_a_fresh_jit_once(self):
        """``utils.tracing.CompileSpans``: one ``jax.compile`` span with
        ``fun`` for a fresh ``jit``, none for its second call, none
        once closed — for any program, registered or not."""
        import jax.numpy as jnp

        from fedtorch_tpu.utils.tracing import CompileSpans

        def fresh_program_for_compile_spans(x):
            return jnp.tanh(x) * 3.0

        def compiles(rec):
            return [e for e in rec.to_trace_events()
                    if e["name"] == "jax.compile" and "fresh_program"
                    in e["args"]["fun"]]

        rec = SpanRecorder()
        spans = CompileSpans(rec).install()
        try:
            f = jax.jit(fresh_program_for_compile_spans)
            f(jnp.ones((3,))).block_until_ready()
            assert len(compiles(rec)) == 1
            names = {e["name"] for e in rec.to_trace_events()}
            assert {"jax.trace", "jax.lower", "jax.compile"} <= names
            one = compiles(rec)[0]
            assert one["dur"] > 0 and one["ts"] >= 0
            f(jnp.ones((3,))).block_until_ready()
            assert len(compiles(rec)) == 1
        finally:
            spans.close()
        spans.close()                      # idempotent
        f(jnp.ones((5,))).block_until_ready()   # a new shape compiles
        assert len(compiles(rec)) == 1     # ... unseen: the listener left

    def test_a_span_that_asks_carries_the_resident_set(self):
        """``span(...).rss()``: ``vm_rss_enter`` and ``vm_rss_exit``
        in bytes as args at exit beside the span's own and its notes; a
        span that does not ask records none, whatever ran inside it."""
        import numpy as np
        rec = SpanRecorder()
        with rec.span("quiet", round=1):
            np.ones(1 << 22, np.uint8)
        with rec.span("asks", round=2).rss() as sp:
            # 64 MiB of fresh memory, every page touched, and held
            held = np.ones(1 << 26, np.uint8)
            sp.note(bytes=held.nbytes)
        quiet, asks = (e for e in rec.to_trace_events() if e["ph"] == "X")
        assert quiet["args"] == {"round": 1}
        assert asks["args"]["round"] == 2
        assert asks["args"]["bytes"] == 1 << 26
        if os.path.exists("/proc/self/status"):
            assert set(asks["args"]) == {"round", "bytes", "vm_rss_enter",
                                         "vm_rss_exit"}
            assert type(asks["args"]["vm_rss_exit"]) is int
            assert asks["args"]["vm_rss_enter"] > 1 << 20   # bytes, not kB
            # what the span's work left resident (the rest of the
            # process may give a little back meanwhile)
            grown = asks["args"]["vm_rss_exit"] - asks["args"]["vm_rss_enter"]
            assert grown >= 1 << 25
        json.dumps(asks)       # rides trace.json as every other arg

    def test_the_resident_set_is_simply_absent_off_linux(self, monkeypatch):
        """No ``/proc/self/status``, or one with no ``VmRSS`` line: the
        span records its own args and no ``vm_*`` one, and does not
        fail."""
        from fedtorch_tpu.telemetry import spans as spans_mod
        rec = SpanRecorder()
        monkeypatch.setattr(spans_mod, "_PROC_STATUS",
                            "/nonexistent/status")
        with rec.span("eval", round=3).rss():
            pass
        with rec.span("checkpoint").rss() as sp:
            sp.note(ok=True)
        monkeypatch.setattr(spans_mod, "_PROC_STATUS", os.devnull)
        with rec.span("eval").rss():
            pass
        assert [e.get("args") for e in rec.to_trace_events()
                if e["ph"] == "X"] == [{"round": 3}, {"ok": True}, None]

    def test_the_resident_set_costs_nothing_where_nobody_asks(self):
        """The disabled path is the one shared no-op, asked or not, and
        a recorded span that does not ask is the plain class (two clock
        reads and an append)."""
        from fedtorch_tpu.telemetry import spans as spans_mod
        assert telemetry.NULL_SPAN.rss() is telemetry.NULL_SPAN
        assert telemetry.span("eval").rss() is telemetry.NULL_SPAN
        rec = SpanRecorder()
        assert type(rec.span("round")) is spans_mod._Span
        assert type(rec.span("round").rss()) is spans_mod._RssSpan

    def test_buffer_bound_counts_drops(self):
        rec = SpanRecorder(max_events=2)
        for _ in range(5):
            with rec.span("s"):
                pass
        assert len(rec._events) == 2 and rec.dropped == 3

    def test_module_hooks_inert_without_active_instance(self):
        assert telemetry.get_active() is None
        with telemetry.span("anything", round=1):
            pass  # must not raise, must not record anywhere
        telemetry.event("anything")
        telemetry.instant("anything")

    def test_off_level_creates_no_files(self, tmp_path):
        tel = Telemetry(str(tmp_path), level="off")
        tel.install()
        try:
            assert telemetry.get_active() is None
            with tel.span("x"):
                pass
            tel.round_row(dict(VALID_ROW))
            tel.health_update("running", round_idx=1)
        finally:
            tel.close()
        assert os.listdir(str(tmp_path)) == []

    def test_bad_level_rejected(self, tmp_path):
        with pytest.raises(ValueError, match="level"):
            Telemetry(str(tmp_path), level="verbose")


# -- the evaluation's spans (library code: the module-level hook) -----------
class TestEvaluateSpans:
    @staticmethod
    def _case():
        from fedtorch_tpu.models import define_model
        rng = np.random.default_rng(0)
        cfg = ExperimentConfig(
            data=DataConfig(dataset="synthetic", synthetic_dim=10,
                            batch_size=8),
            model=ModelConfig(arch="logistic_regression")).finalize()
        model = define_model(cfg, batch_size=8)
        params = model.init(jax.random.key(0))
        x = rng.normal(size=(300, 10)).astype(np.float32)
        y = rng.integers(0, 2, size=300).astype(np.int32)
        return model, params, x, y

    def test_four_spans_with_a_run_installed(self, tmp_path):
        from fedtorch_tpu.parallel.evaluate import evaluate_to_host
        model, params, x, y = self._case()
        with Telemetry(str(tmp_path)) as tel:
            with tel.span("eval", round=0).rss():
                res = evaluate_to_host(model, params, x, y)
            spans = [e for e in tel.spans.to_trace_events()
                     if e["ph"] == "X"]
        assert isinstance(res.top1, np.ndarray)     # on the host
        assert [e["name"] for e in spans] == [
            "eval.batches", "eval.h2d", "eval.dispatch", "eval.fetch",
            "eval"]
        batches, h2d, dispatch, fetch, whole = spans
        # 300 rows padded to two batches of 256: float32 features,
        # int32 labels, float64 mask
        want = 512 * (10 * 4 + 4 + 8)
        assert batches["args"] == {"bytes": want, "rows": 300,
                                   "pad_rows": 212}
        assert h2d["args"] == {"bytes": want}
        assert "args" not in dispatch and "args" not in fetch
        edges = [t for e in spans[:4] for t in (e["ts"], e["ts"] + e["dur"])]
        assert edges == sorted(edges)               # one after the other
        assert whole["ts"] <= edges[0] \
            and edges[-1] <= whole["ts"] + whole["dur"]
        assert whole["args"]["round"] == 0
        assert set(whole["args"]) <= {"round", "vm_rss_enter",
                                      "vm_rss_exit"}

    def test_no_run_installed_records_nothing_and_agrees(self, tmp_path):
        from fedtorch_tpu.parallel.evaluate import (
            evaluate, evaluate_to_host,
        )
        model, params, x, y = self._case()
        assert telemetry.get_active() is None
        bare = evaluate_to_host(model, params, x, y)
        with Telemetry(str(tmp_path)):
            spanned = jax.device_get(evaluate(model, params, x, y))
        for a, b in zip(bare, spanned):
            np.testing.assert_array_equal(a, b)


# -- health.json -------------------------------------------------------------
class TestHealthFile:
    def test_write_validates_and_reads_back(self, tmp_path):
        hf = HealthFile(health_path(str(tmp_path)))
        doc = hf.update("running", round_idx=7)
        validate_health(doc)
        got = read_health(str(tmp_path))
        assert got["round"] == 7 and got["intent"] == "running"
        assert got["schema"] == HEALTH_SCHEMA

    def test_progress_stamp_advances_only_with_round(self, tmp_path):
        t = {"now": 100.0}
        hf = HealthFile(str(tmp_path / "health.json"),
                        clock=lambda: t["now"], min_interval_s=0.0)
        hf.update("running", round_idx=1)
        t["now"] = 150.0
        doc = hf.update("running", round_idx=1)  # no progress
        assert doc["since_progress_s"] == 50.0
        doc = hf.update("running", round_idx=2)  # progress
        assert doc["since_progress_s"] == 0.0

    def test_throttle_skips_disk_but_intent_change_writes(self, tmp_path):
        t = {"now": 0.0}
        hf = HealthFile(str(tmp_path / "health.json"),
                        clock=lambda: t["now"], min_interval_s=1.0)
        hf.update("running", round_idx=0)
        for r in range(1, 5):
            t["now"] += 0.01  # 100 rounds/s — faster than the throttle
            hf.update("running", round_idx=r)
        assert hf.writes == 1 and hf.throttled == 4
        # intent flip bypasses the throttle (a drain must be visible
        # immediately) ...
        hf.update("drain", round_idx=4)
        assert hf.writes == 2
        # ... and the elapsed interval lets a round update through
        t["now"] += 1.5
        hf.update("drain", round_idx=5)
        assert hf.writes == 3

    def test_read_missing_returns_none(self, tmp_path):
        assert read_health(str(tmp_path)) is None

    def test_schema_skew_raises(self, tmp_path):
        with open(tmp_path / "health.json", "w") as f:
            json.dump({"schema": "fedtorch_tpu.health/v999"}, f)
        with pytest.raises(ValueError, match="health schema"):
            read_health(str(tmp_path))

    def test_unknown_intent_rejected(self, tmp_path):
        hf = HealthFile(str(tmp_path / "health.json"))
        doc = hf.update("running", round_idx=1)
        doc["intent"] = "confused"
        with pytest.raises(ValueError, match="intent"):
            validate_health(doc)

    def test_write_error_counted_not_raised(self, tmp_path):
        (tmp_path / "blocked").write_text("")
        hf = HealthFile(str(tmp_path / "blocked" / "health.json"))
        hf.update("running", round_idx=1)
        assert hf.write_errors == 1


# -- host-only: trace-once + bitwise trajectory + HLO identity ---------------
PLANES = [("device", "sync"), ("stream", "sync"),
          ("device", "async"), ("stream", "async")]


class TestHostOnly:
    @pytest.mark.parametrize("plane,sync_mode", PLANES)
    def test_trajectory_bitwise_and_traces_once(self, plane, sync_mode,
                                                tmp_path):
        """Telemetry on vs off: identical bits, one trace — across
        both data planes and both federation modes (the acceptance
        matrix). The telemetry-on leg emits real rows/spans/health so
        the instrumented paths actually execute."""
        ref = run_rounds_collect(
            make_trainer(plane=plane, sync_mode=sync_mode), 4)

        trainer = make_trainer(plane=plane, sync_mode=sync_mode)
        tel = Telemetry(str(tmp_path), level="default")
        tel.install()
        try:
            server, clients = trainer.init_state(jax.random.key(0))
            got = []
            with RecompilationSentinel() as s:
                for r in range(4):
                    with tel.span("round", round=r):
                        server, clients, m = trainer.run_round(
                            server, clients)
                    sc = trainer.round_host_scalars(clients, m)
                    n = max(sc["n_online"], 1.0)
                    row = dict(VALID_ROW, round=r,
                               loss=sc["loss_sum"] / n,
                               acc=sc["acc_sum"] / n, lr=sc["lr"],
                               n_online=sc["n_online"],
                               comm_bytes=sc["comm_bytes"],
                               staleness=sc["staleness"])
                    row.update(trainer.telemetry_gauges())
                    validate_metrics_row(row)
                    tel.round_row(row)
                    tel.health_update("running", round_idx=r + 1)
                    got.append(np.concatenate([
                        np.ravel(x) for x in jax.tree.leaves(
                            jax.device_get(server.params))]))
            trainer.invalidate_stream()
            name = {
                ("device", "sync"): "trace_name",
                ("stream", "sync"): "stream_trace_name",
                ("device", "async"): "commit_trace_name",
                ("stream", "async"): "commit_stream_trace_name",
            }[(plane, sync_mode)]
            s.assert_traces(getattr(trainer, name), expected=1)
        finally:
            tel.close()
        for a, b in zip(ref, got):
            np.testing.assert_array_equal(a, b)
        # the run left parseable telemetry behind
        rows = [r for r in iter_jsonl(str(tmp_path / "metrics.jsonl"))
                if "schema" not in r]
        assert len(rows) == 4
        # sub-second rounds: the disk document lags behind the health
        # throttle, but the intent flip below (like the real loop's
        # end-of-run update) writes through with the latest round
        tel2 = Telemetry(str(tmp_path), level="default")
        tel2.health_update("complete", round_idx=4)
        tel2.close()
        h = read_health(str(tmp_path))
        assert h["round"] == 4 and h["intent"] == "complete"

    def test_round_program_hlo_identical_with_telemetry_active(
            self, tmp_path):
        """The traced program cannot depend on telemetry (it is
        host-only by construction) — pinned byte-for-byte like the
        watchdog's zero-overhead bar."""
        texts = []
        for level in (None, "default"):
            trainer = make_trainer()
            tel = None
            if level:
                tel = Telemetry(str(tmp_path), level=level)
                tel.install()
            try:
                server, clients = trainer.init_state(jax.random.key(0))
                lowered = trainer._round_jit.lower(
                    server, clients, trainer.data, trainer.val_data)
                texts.append(lowered.as_text())
            finally:
                if tel is not None:
                    tel.close()
        assert texts[0] == texts[1]


# -- run_experiment integration + report tool --------------------------------
def _cli_cfg(run_dir, rounds=4, extra=()):
    from fedtorch_tpu.cli import args_to_config, build_parser
    argv = [
        "--federated", "true", "-d", "synthetic", "-a",
        "logistic_regression", "--num_comms", str(rounds),
        "--num_workers", "6", "--online_client_rate", "0.5",
        "--federated_sync_type", "local_step", "--local_step", "2",
        "--batch_size", "8", "--lr", "0.1", "--eval_freq", "2",
        "--debug", "false", "--run_dir", run_dir]
    argv.extend(extra)
    return args_to_config(build_parser().parse_args(argv))


class TestRunDirAndReport:
    def test_mini_run_emits_all_three_pillars(self, tmp_path, capsys):
        from fedtorch_tpu.cli import run_experiment
        from fedtorch_tpu.tools.report import render, summarize
        run_dir = str(tmp_path / "run")
        res = run_experiment(_cli_cfg(run_dir, rounds=4,
                                      extra=("--async_checkpoint",)))
        assert "test_top1" in res

        # pillar 1: schema-valid metrics rows + events
        rows = [r for r in iter_jsonl(os.path.join(run_dir,
                                                   "metrics.jsonl"))
                if "schema" not in r]
        assert [r["round"] for r in rows] == [0, 1, 2, 3]
        for r in rows:
            validate_metrics_row(r)
        # eval rounds carry the eval/checkpoint phases + test acc;
        # the async checkpointer's gauges ride the row
        evals = [r for r in rows if "test_top1" in r]
        assert [r["round"] for r in evals] == [1, 3]
        assert all("eval_s" in r and "checkpoint_s" in r
                   for r in evals)
        assert "ckpt_queue_depth" in rows[-1]
        names = [e["event"] for e in iter_jsonl(
            os.path.join(run_dir, "events.jsonl")) if "schema" not in e]
        assert names[0] == "run.start" and names[-1] == "run.end"

        # pillar 2: Perfetto-loadable host spans
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        span_names = {e["name"] for e in doc["traceEvents"]}
        assert {"round", "scalar_fetch", "eval",
                "checkpoint.snapshot", "checkpoint.write",
                "checkpoint.serialize", "checkpoint.file_write",
                "checkpoint.link", "data.build"} <= span_names

        # pillar 3: health reached 'complete' at the final round
        h = read_health(run_dir)
        assert h["intent"] == "complete" and h["round"] == 4

        # the report tool renders the dir (telemetry source)
        s = summarize(run_dir)
        assert s["source"] == "telemetry"
        assert s["rounds"] == 4
        assert s["meta"]["algorithm"] == "fedavg"
        assert s["comm_bytes_total"] == sum(
            r["comm_bytes"] for r in rows)
        assert {p[0] for p in s["phases"]} == {
            "round", "scalar_fetch", "eval", "checkpoint"}
        assert s["final_test_top1"] == evals[-1]["test_top1"]
        out = render(run_dir)
        assert "phase breakdown" in out and "intent=complete" in out

        # CLI routing: `fedtorch-tpu report <dir>` prints it
        from fedtorch_tpu.cli import main
        assert main(["report", run_dir]) == 0
        assert "phase breakdown" in capsys.readouterr().out

    def test_spans_lie_in_the_profile_on_its_own_clock(self, tmp_path):
        """A launcher run under ``jax.profiler``: every recorder span
        also opened a ``TraceAnnotation``, so the host plane of the
        ``.xplane.pb`` holds as many ``round`` / ``scalar_fetch`` /
        ``round.record`` events as ``trace.json``, in the same order
        and with the round in their stats — the one clock the
        benchmark's gap labels are read on."""
        import glob

        from jax.profiler import ProfileData, ProfileOptions

        from fedtorch_tpu.cli import run_experiment
        run_dir, prof_dir = str(tmp_path / "run"), str(tmp_path / "prof")
        opts = ProfileOptions()
        opts.python_tracer_level = 0     # annotations only: a small file
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        try:
            run_experiment(_cli_cfg(run_dir, rounds=2))
        finally:
            jax.profiler.stop_trace()
        names = ("round", "scalar_fetch", "round.record")
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        recorded = sorted(
            (e["ts"], e["name"], e["args"]["round"])
            for e in doc["traceEvents"]
            if e.get("ph") == "X" and e["name"] in names)
        prof = ProfileData.from_file(glob.glob(os.path.join(
            prof_dir, "plugins", "profile", "*", "*.xplane.pb"))[0])
        # jaxlib's iterator over an event's stats raises a
        # DeprecationWarning when it is first made (nanobind: no
        # ``__module__``); as an error it aborts the interpreter from
        # inside the extension
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            annotated = sorted(
                (ev.start_ns, ev.name, dict(ev.stats)["round"])
                for plane in prof.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name in names)
        assert len(recorded) == 2 * 4    # two record spans a round
        assert [r[1:] for r in recorded] == [a[1:] for a in annotated]
        # one clock: the annotations' spacing is the recorder's
        rec_gap = (recorded[-1][0] - recorded[0][0]) * 1e3      # us -> ns
        ann_gap = annotated[-1][0] - annotated[0][0]
        assert abs(rec_gap - ann_gap) < 5e6

    def test_dispatch_and_wait_lie_inside_round(self, tmp_path):
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=2))
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        by_round = {}
        for e in doc["traceEvents"]:
            if e.get("ph") == "X" and e["name"] in (
                    "round", "round.dispatch", "round.wait"):
                by_round.setdefault(e["args"]["round"], {})[e["name"]] = (
                    e["ts"], e["ts"] + e["dur"])
        assert sorted(by_round) == [0, 1]
        for spans in by_round.values():
            r0, r1 = spans["round"]
            d0, d1 = spans["round.dispatch"]
            w0, w1 = spans["round.wait"]
            assert r0 <= d0 <= d1 <= w0 <= w1 <= r1
        # the set-up spans: the store's placement inside the trainer's
        # build, the loader's parts inside data.build
        spans = {e["name"]: (e["ts"], e["ts"] + e["dur"])
                 for e in doc["traceEvents"] if e.get("ph") == "X"}
        for child, parent in (("data.h2d", "trainer.build"),
                              ("data.load", "data.build"),
                              ("data.partition", "data.build"),
                              ("data.layout", "data.build")):
            assert spans[parent][0] <= spans[child][0] \
                and spans[child][1] <= spans[parent][1], (child, parent)
        assert "state.init" in spans

    def test_the_loops_eval_and_checkpoint_carry_the_resident_set(
            self, tmp_path):
        """A launcher run: the loop's ``eval`` and ``checkpoint`` carry
        ``vm_rss_enter`` and ``vm_rss_exit``, the evaluation's four
        children lie inside ``eval`` and the save's tree inside
        ``checkpoint``; the spans that do not ask carry none."""
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=2))
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        named = lambda n: [e for e in spans if e["name"] == n]
        for name in ("round", "round.dispatch", "round.wait",
                     "scalar_fetch", "round.record"):
            # one a round (two for ``round.record``)
            assert len(named(name)) == (4 if name == "round.record" else 2)
            assert all(set(e["args"]) == {"round"} for e in named(name))
        inside = lambda c, p: p["ts"] <= c["ts"] and \
            c["ts"] + c["dur"] <= p["ts"] + p["dur"]
        for parent, children in (
                ("eval", ("eval.batches", "eval.h2d", "eval.dispatch",
                          "eval.fetch")),
                ("checkpoint", (
                    "checkpoint.snapshot", "checkpoint.layout",
                    "checkpoint.digest", "checkpoint.file_write.data",
                    "checkpoint.file_write.fsync",
                    "checkpoint.file_write.rename", "checkpoint.link"))):
            (whole,) = named(parent)        # eval_freq 2: after round 1
            vm = {"vm_rss_enter", "vm_rss_exit"} \
                if os.path.exists("/proc/self/status") else set()
            assert set(whole["args"]) == {"round"} | vm
            for child in children:
                assert named(child), child
                assert all(inside(c, whole) for c in named(child)), child

    def test_compile_listeners_leave_with_the_telemetry(self, tmp_path):
        from jax._src import monitoring

        from fedtorch_tpu.cli import run_experiment
        before = (len(monitoring.get_event_time_span_listeners()),
                  len(monitoring.get_event_duration_listeners()),
                  len(monitoring.get_event_listeners()))
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=2))
        assert before == (
            len(monitoring.get_event_time_span_listeners()),
            len(monitoring.get_event_duration_listeners()),
            len(monitoring.get_event_listeners()))
        # the run's own programs were seen: round 0 compiled inside
        # its ``round`` span, under the name JAX gives it
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        evs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
        r0 = next(e for e in evs if e["name"] == "round"
                  and e["args"]["round"] == 0)
        inside = [e for e in evs if e["name"] == "jax.compile"
                  and r0["ts"] <= e["ts"]
                  and e["ts"] + e["dur"] <= r0["ts"] + r0["dur"]]
        assert any("round_fn" in e["args"]["fun"] for e in inside)

    def test_byzantine_run_report_and_events(self, tmp_path):
        """ISSUE 9: an attacked run lands the one-shot
        chaos.byzantine_attack event, schema-valid byzantine/robust
        counters on every row, and a rendered Robustness section."""
        from fedtorch_tpu.cli import run_experiment
        from fedtorch_tpu.tools.report import render, summarize
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(
            run_dir, rounds=3,
            extra=("--fault_byzantine_rate", "0.5",
                   "--fault_byzantine_scale", "2.0",
                   "--robust_agg", "median", "--guard_updates", "true")))
        rows = [r for r in iter_jsonl(os.path.join(run_dir,
                                                   "metrics.jsonl"))
                if "schema" not in r]
        for r in rows:
            validate_metrics_row(r)
        assert sum(r["byzantine"] for r in rows) > 0
        assert sum(r["robust_selected"] for r in rows) > 0
        events = [e for e in iter_jsonl(os.path.join(run_dir,
                                                     "events.jsonl"))
                  if "schema" not in e]
        atk = [e for e in events
               if e["event"] == "chaos.byzantine_attack"]
        assert len(atk) == 1  # once per run, not per round
        assert atk[0]["mode"] == "sign_flip"
        assert atk[0]["robust_agg"] == "median"
        s = summarize(run_dir)
        assert s["robustness"]["byzantine"]["total"] > 0
        assert s["robustness"]["attack"]["robust_agg"] == "median"
        out = render(run_dir)
        assert "robustness" in out and "byzantine uploads injected" \
            in out

    def test_all_rejected_run_emits_event(self, tmp_path):
        """A round whose every update is guard-rejected (100% NaN
        injection) emits guards.all_rejected — the renorm-scale-0
        blind spot this PR closes."""
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(
            run_dir, rounds=2,
            extra=("--fault_nan_inject_rate", "1.0",
                   "--guard_updates", "true")))
        events = [e for e in iter_jsonl(os.path.join(run_dir,
                                                     "events.jsonl"))
                  if "schema" not in e]
        rejected = [e for e in events
                    if e["event"] == "guards.all_rejected"]
        assert len(rejected) == 2
        assert rejected[0]["round"] == 0

    def test_report_falls_back_to_record0(self, tmp_path):
        # pre-telemetry run dirs (legacy record0 only) stay renderable
        from fedtorch_tpu.tools.report import summarize
        run_dir = tmp_path / "legacy"
        run_dir.mkdir()
        lines = [
            "Round: 1. Epoch: 1.00. Local index: 10. Load: 0.1s | "
            "Computing: 2.0s | Sync: 0.1s | Global: 2.2s | "
            "Loss: 1.5 | top1: 40.0 | lr: 0.1 | CommBytes: 1000.0",
            "Round: 2. Epoch: 2.00. Local index: 20. Load: 0.1s | "
            "Computing: 1.0s | Sync: 0.1s | Global: 1.2s | "
            "Loss: 1.0 | top1: 60.0 | lr: 0.1 | CommBytes: 1000.0",
            "Round: 2. Mode: test. Loss: 0.9 | top1: 61.0 | "
            "top5: 91.0",
        ]
        (run_dir / "record0").write_text("\n".join(lines) + "\n")
        s = summarize(str(run_dir))
        assert s["source"] == "record0"
        assert s["rounds"] == 2
        assert s["final_test_top1"] == 61.0

    def test_report_on_non_run_dir_errors(self, tmp_path):
        from fedtorch_tpu.cli import main
        assert main(["report", str(tmp_path)]) == 2

    def test_telemetry_off_writes_no_files(self, tmp_path):
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=2,
                                extra=("--telemetry", "off")))
        present = set(os.listdir(run_dir))
        assert not present & {"metrics.jsonl", "events.jsonl",
                              "health.json", "trace.json"}


# -- health atomicity under the SIGTERM drain drill --------------------------
class TestHealthUnderDrain:
    def test_drain_drill_health_never_torn(self, tmp_path):
        """A poller hammering health.json THROUGH a SIGTERM drain must
        only ever see complete documents (os.replace atomicity), and
        the final intent is 'preempted' — the machine-readable exit
        the harness logs."""
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        os.makedirs(run_dir)
        stop = threading.Event()
        seen = {"docs": 0, "intents": set()}
        failures = []

        def poll():
            path = health_path(run_dir)
            while not stop.is_set():
                try:
                    with open(path) as f:
                        raw = f.read()
                except OSError:
                    continue  # not yet written
                if not raw:
                    failures.append("empty read")  # torn replace
                    continue
                try:
                    doc = json.loads(raw)
                    validate_health(doc)
                except ValueError as e:
                    failures.append(f"torn/invalid: {e}")
                    continue
                seen["docs"] += 1
                seen["intents"].add(doc["intent"])

        poller = threading.Thread(target=poll, daemon=True)
        poller.start()

        def cb(r, trainer, server, clients, metrics):
            if r == 1:
                os.kill(os.getpid(), signal.SIGTERM)

        try:
            res = run_experiment(_cli_cfg(run_dir, rounds=6),
                                 round_callback=cb)
        finally:
            stop.set()
            poller.join(timeout=10)
        assert res["preempted"]
        assert not failures, failures[:5]
        assert seen["docs"] > 0
        final = read_health(run_dir)
        assert final["intent"] == "preempted"
        # the drain transition was written through (intent flips
        # bypass the health throttle)
        assert "drain" in seen["intents"] or final["round"] >= 2
        # the restart harness reads the same contract
        from fedtorch_tpu.robustness.harness import read_exit_intent
        assert read_exit_intent(run_dir) == "preempted"

    def test_loop_error_lands_error_intent(self, tmp_path):
        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")

        def cb(r, trainer, server, clients, metrics):
            raise RuntimeError("boom")

        with pytest.raises(RuntimeError, match="boom"):
            run_experiment(_cli_cfg(run_dir, rounds=3),
                           round_callback=cb)
        assert read_health(run_dir)["intent"] == "error"
