"""Device-side observability (docs/observability.md "Device-side",
ISSUE 8): compiled-program cost capture, profiler-trace attribution,
and the per-round measured-MFU / HBM gauges.

The contracts made executable here:

* ``program_costs.json`` is schema-versioned and validated like the
  metrics row (uncataloged fields rejected, graceful ``None`` for
  backend-silent statistics);
* cost capture is HOST-ONLY: the uninstrumented twins lower to HLO
  byte-identical to the live round/commit programs, and with capture +
  MFU gauges enabled the programs still trace exactly once — across
  device/stream planes x sync/async modes;
* the trace attributor buckets >= 95% of device time into named
  categories on the checked-in fixture AND on a real CPU-backend
  capture, handles malformed/empty traces, and renders through
  ``fedtorch-tpu report --device``.
"""
import gzip
import json
import os

import jax
import pytest

from fedtorch_tpu.telemetry import validate_metrics_row
from fedtorch_tpu.telemetry.costs import (
    FLOPS_XLA, PROGRAM_COSTS_SCHEMA, ProgramCostCapture, cost_summary,
    lowered_cost, read_program_costs, resolve_peak_tflops,
    validate_program_costs,
)
from fedtorch_tpu.tools import trace_attrib
from fedtorch_tpu.utils.tracing import RecompilationSentinel
from test_telemetry import make_trainer

FIXTURE_DIR = os.path.join(os.path.dirname(__file__), "data",
                           "device_attrib")

PLANES = [("device", "sync"), ("stream", "sync"),
          ("device", "async"), ("stream", "async")]

TRACE_NAMES = {
    ("device", "sync"): "trace_name",
    ("stream", "sync"): "stream_trace_name",
    ("device", "async"): "commit_trace_name",
    ("stream", "async"): "commit_stream_trace_name",
}


def capture_for(trainer, tmp_path, **kw):
    # stamped as the one part the peaks table lists, so the gauge
    # ARITHMETIC is exercised; what a cpu stamp yields is pinned in
    # test_no_mfu_without_a_published_peak
    kw.setdefault("device_kind", "TPU v5 lite")
    kw.setdefault("compute_dtype", "bfloat16")
    cap = ProgramCostCapture(
        str(tmp_path),
        arch="logistic_regression", batch_size=8,
        local_steps=trainer.local_steps, k_online=trainer.k_online,
        num_devices=int(trainer.mesh.devices.size), backend="cpu",
        **kw)
    return cap


# -- program_costs.json schema ----------------------------------------------
class TestProgramCostsSchema:
    def test_capture_roundtrip_validates(self, tmp_path):
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        programs, primary = trainer.lowered_cost_programs(
            server, clients, num_scan_rounds=2)
        assert primary == "round"
        assert set(programs) == {"round", "rounds_scan[2]"}
        cap = capture_for(trainer, tmp_path)
        doc = cap.capture(programs, primary=primary)
        assert doc is not None and cap.captured
        got = read_program_costs(str(tmp_path))
        assert got["schema"] == PROGRAM_COSTS_SCHEMA
        assert got["primary"] == "round"
        # the CPU backend reports real costs: flops positive, the scan
        # of 2 rounds costs more than one round
        r = got["programs"]["round"]
        assert r["flops"] > 0 and r["flops_source"] == FLOPS_XLA
        assert r["peak_hbm_bytes"] > 0 and r["bytes_accessed"] > 0
        assert got["programs"]["rounds_scan[2]"]["flops"] > r["flops"]

    def test_uncataloged_program_field_rejected(self, tmp_path):
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        programs, primary = trainer.lowered_cost_programs(server,
                                                          clients)
        doc = capture_for(trainer, tmp_path).capture(programs,
                                                     primary=primary)
        doc["programs"]["round"]["my_new_stat"] = 1.0
        with pytest.raises(ValueError, match="uncataloged"):
            validate_program_costs(doc)
        del doc["programs"]["round"]["my_new_stat"]
        doc["surprise"] = True
        with pytest.raises(ValueError, match="uncataloged"):
            validate_program_costs(doc)

    def test_missing_required_and_schema_skew_rejected(self):
        with pytest.raises(ValueError, match="schema"):
            validate_program_costs({"schema": "fedtorch_tpu/v999"})
        doc = {"schema": PROGRAM_COSTS_SCHEMA, "created_unix": 0.0,
               "backend": "cpu", "num_devices": 1,
               "compute_dtype": "float32",
               "peak_tflops_per_chip": 98.0, "peak_source": "x",
               "programs": {"round": {"flops": 1.0}}}
        validate_program_costs(doc)
        del doc["peak_source"]
        with pytest.raises(ValueError, match="peak_source"):
            validate_program_costs(doc)
        doc["peak_source"] = "x"
        doc["programs"] = {}
        with pytest.raises(ValueError, match="non-empty"):
            validate_program_costs(doc)

    def test_graceful_none_on_dead_backend(self):
        # a Lowered whose compile explodes must yield the all-None
        # summary (+ error note) — and still validate
        class Dead:
            def compile(self):
                raise RuntimeError("backend gone")

        rec = lowered_cost(Dead())
        assert rec["flops"] is None and rec["flops_source"] is None
        assert "backend gone" in rec["error"]
        validate_program_costs({
            "schema": PROGRAM_COSTS_SCHEMA, "created_unix": 0.0,
            "backend": None, "num_devices": 1,
            "compute_dtype": "float32", "peak_tflops_per_chip": 98.0,
            "peak_source": "x", "programs": {"round": rec}})
        assert cost_summary(None)["flops"] is None

    def test_peak_resolution(self):
        # a table keyed by device_kind; anything it does not list —
        # the CPU, an unknown part, a dtype with no published peak —
        # has NO peak, never another part's number
        assert resolve_peak_tflops("TPU v5 lite", "bfloat16") == (
            197.0, "table:TPU v5 lite:bfloat16")
        for kind, dtype in (("cpu", "bfloat16"), ("cpu", "float32"),
                            ("TPU v99", "bfloat16"), (None, "bfloat16"),
                            ("TPU v5 lite", "float32")):
            peak, source = resolve_peak_tflops(kind, dtype)
            assert peak is None and source.startswith("none:")

    def test_no_mfu_without_a_published_peak(self, tmp_path):
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        programs, primary = trainer.lowered_cost_programs(server,
                                                          clients)
        cap = capture_for(trainer, tmp_path,
                          device_kind=jax.devices()[0].device_kind)
        doc = cap.capture(programs, primary=primary)
        assert doc["peak_tflops_per_chip"] is None
        gauges = cap.round_gauges(0.25)
        assert not {"model_flops_utilization", "round_device_min_s",
                    "round_host_frac"} & set(gauges)
        assert gauges["hbm_program_peak_bytes"] > 0


# -- host-only: trace-once + byte-identical HLO -----------------------------
class TestCostCaptureHostOnly:
    @pytest.mark.parametrize("plane,sync_mode", PLANES)
    def test_capture_mid_loop_traces_once(self, plane, sync_mode,
                                          tmp_path):
        trainer = make_trainer(plane=plane, sync_mode=sync_mode)
        server, clients = trainer.init_state(jax.random.key(0))
        cap = capture_for(trainer, tmp_path)
        with RecompilationSentinel() as s:
            server, clients, m = trainer.run_round(server, clients)
            programs, primary = trainer.lowered_cost_programs(server,
                                                              clients)
            cap.capture(programs, primary=primary)
            server, clients, m = trainer.run_round(server, clients)
        trainer.invalidate_stream()
        s.assert_traces(getattr(trainer, TRACE_NAMES[(plane,
                                                      sync_mode)]),
                        expected=1)
        doc = read_program_costs(str(tmp_path))
        assert doc["primary"] == primary
        assert doc["programs"][primary]["flops"] > 0
        gauges = cap.round_gauges(0.5)
        assert gauges["model_flops_utilization"] > 0
        assert gauges["hbm_program_peak_bytes"] > 0
        assert gauges["hbm_live_bytes"] > 0
        validate_metrics_row(dict(
            {"round": 0, "round_s": 0.5, "loss": 1.0, "acc": 0.5,
             "lr": 0.1, "n_online": 4.0, "comm_bytes": 1e6}, **gauges))

    def test_twin_hlo_byte_identical_device_sync(self):
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        live = trainer._round_jit.lower(
            server, clients, trainer.data, trainer.val_data).as_text()
        twin = trainer.lowered_cost_programs(server, clients)[0][
            "round"].as_text()
        assert live == twin

    def test_twin_hlo_byte_identical_stream(self):
        trainer = make_trainer(plane="stream")
        server, clients = trainer.init_state(jax.random.key(0))
        feed = trainer._next_stream_feed(server)
        live = trainer._round_stream_jit.lower(server, clients,
                                               feed).as_text()
        twin = trainer.lowered_cost_programs(server, clients)[0][
            "round_stream"].as_text()
        trainer.invalidate_stream()
        assert live == twin

    def test_twin_hlo_byte_identical_async_commit(self):
        from fedtorch_tpu.async_plane.commit import CommitJobs
        trainer = make_trainer(sync_mode="async")
        server, clients = trainer.init_state(jax.random.key(0))
        trainer._ensure_schedule(server)
        plan = trainer._sched.next_commit()
        jobs = CommitJobs(idx=plan.idx, version=plan.version,
                          dispatch=plan.dispatch,
                          straggler=plan.straggler)
        live = trainer._commit_jit.lower(server, clients, jobs,
                                         trainer.data).as_text()
        twin = trainer.lowered_cost_programs(server, clients)[0][
            "commit"].as_text()
        trainer.invalidate_stream()
        assert live == twin

    def test_mfu_gauge_definition(self, tmp_path):
        # model_flops_utilization == flops / (round_s * peak * chips)
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        programs, primary = trainer.lowered_cost_programs(server,
                                                          clients)
        cap = capture_for(trainer, tmp_path)
        doc = cap.capture(programs, primary=primary)
        flops = doc["programs"]["round"]["flops"]
        n_dev = int(trainer.mesh.devices.size)
        got = cap.round_gauges(0.25)["model_flops_utilization"]
        assert got == pytest.approx(
            flops / (0.25 * 197.0 * 1e12 * n_dev))
        # gauges are empty before a successful capture
        assert capture_for(trainer, tmp_path).round_gauges(0.25) == {}

    def test_resume_adopts_existing_capture(self, tmp_path):
        # elastic restarts reuse the run dir: a second capture object
        # adopts the recorded document instead of recompiling
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        programs, primary = trainer.lowered_cost_programs(server,
                                                          clients)
        capture_for(trainer, tmp_path).capture(programs,
                                               primary=primary)
        cap2 = capture_for(trainer, tmp_path)
        assert cap2.load_existing() and cap2.captured
        assert cap2.round_gauges(0.5)["model_flops_utilization"] > 0
        assert not capture_for(trainer,
                               tmp_path / "fresh").load_existing()
        # a valid doc WITHOUT a usable primary still adopts (gauges
        # off) — half-adopting would pay the resume recompile this
        # path exists to avoid
        doc = json.loads((tmp_path / "program_costs.json").read_text())
        del doc["primary"]
        (tmp_path / "program_costs.json").write_text(json.dumps(doc))
        cap3 = capture_for(trainer, tmp_path)
        assert cap3.load_existing() and cap3.captured
        assert cap3.round_gauges(0.5) == {}

    def test_capture_failure_absorbed(self, tmp_path):
        class Dead:
            def compile(self):
                raise RuntimeError("nope")

        logs = []
        cap = capture_for(make_trainer(), tmp_path,
                          log=lambda m: logs.append(m))
        doc = cap.capture({"round": Dead()}, primary="round")
        # per-program failure still yields a valid document with the
        # error noted; gauges stay off (no flops)
        assert doc is not None
        assert doc["programs"]["round"]["error"]
        assert "model_flops_utilization" not in cap.round_gauges(0.5)


# -- trace attribution: fixture ---------------------------------------------
class TestTraceAttribFixture:
    def test_exact_category_totals(self):
        doc = trace_attrib.attribute(FIXTURE_DIR)
        cats = doc["categories"]
        expect = {"matmul_conv_mxu": 100.0, "elementwise": 60.0,
                  "collective": 30.0, "reduce": 20.0,
                  "copy_reshape_transpose": 10.0,
                  "infeed_outfeed_h2d": 5.0, "other": 5.0,
                  "idle_gap": 10.0}
        assert {c: cats[c]["time_us"] for c in expect} == expect
        assert doc["total_us"] == 240.0
        assert doc["span_us"] == 240.0 and doc["busy_us"] == 230.0
        assert doc["device_lanes"] == 1 and doc["device_events"] == 8
        # the python-lane PjitFunction event was never selected
        assert "PjitFunction" not in {o["name"] for o in doc["top_ops"]}

    def test_attribution_invariant(self):
        doc = trace_attrib.attribute(FIXTURE_DIR)
        assert doc["attributed_frac"] == pytest.approx(1 - 5.0 / 240.0)
        assert doc["attributed_ok"]

    def test_invariant_flags_unknown_heavy_trace(self, tmp_path):
        evs = [{"ph": "X", "pid": 1, "tid": 1, "name": "mystery.1",
                "ts": 0.0, "dur": 90.0, "args": {"hlo_op": "mystery.1"}},
               {"ph": "X", "pid": 1, "tid": 1, "name": "dot.1",
                "ts": 90.0, "dur": 10.0, "args": {"hlo_op": "dot.1"}}]
        p = tmp_path / "bad.trace.json"
        p.write_text(json.dumps({"traceEvents": evs}))
        doc = trace_attrib.attribute(str(p))
        assert doc["attributed_frac"] == pytest.approx(0.1)
        assert not doc["attributed_ok"]

    def test_nested_events_self_time_split(self):
        # a wrapper spanning its children contributes only self time
        evs = [{"ph": "X", "pid": 1, "tid": 1, "name": "while.1",
                "ts": 0.0, "dur": 100.0, "args": {"hlo_op": "while.1"}},
               {"ph": "X", "pid": 1, "tid": 1, "name": "dot.1",
                "ts": 10.0, "dur": 60.0, "args": {"hlo_op": "dot.1"}},
               {"ph": "X", "pid": 1, "tid": 1, "name": "tanh.1",
                "ts": 70.0, "dur": 20.0, "args": {"hlo_op": "tanh.1"}}]
        doc = trace_attrib.attribute_events(evs)
        assert doc["cat_us"]["matmul_conv_mxu"] == 60.0
        assert doc["cat_us"]["elementwise"] == 20.0
        assert doc["cat_us"]["control_flow"] == 20.0  # while self time
        assert doc["idle_us"] == 0.0

    def test_stray_out_of_window_event_not_idle(self):
        # the profiler occasionally flushes a stray pre-window event;
        # a 1us op seconds away must not read as seconds of idle
        evs = [{"ph": "X", "pid": 1, "tid": 1, "name": "reduce.9",
                "ts": 5.0, "dur": 1.0, "args": {"hlo_op": "reduce.9"}},
               {"ph": "X", "pid": 1, "tid": 1, "name": "dot.1",
                "ts": 5e6, "dur": 400.0, "args": {"hlo_op": "dot.1"}},
               {"ph": "X", "pid": 1, "tid": 1, "name": "tanh.1",
                "ts": 5e6 + 410, "dur": 90.0,
                "args": {"hlo_op": "tanh.1"}}]
        doc = trace_attrib.attribute_events(evs)
        assert doc["idle_us"] == pytest.approx(10.0)

    def test_malformed_trace_raises(self, tmp_path):
        p = tmp_path / "broken.trace.json.gz"
        p.write_bytes(gzip.compress(b"{not json"))
        with pytest.raises(ValueError, match="broken"):
            trace_attrib.attribute(str(p))
        q = tmp_path / "noevents.trace.json"
        q.write_text(json.dumps({"foo": 1}))
        with pytest.raises(ValueError, match="traceEvents"):
            trace_attrib.attribute(str(q))

    def test_zero_duration_events_render_na(self, tmp_path, capsys):
        # events selected but no durations: render must say n/a, not
        # crash on the None attributed fraction
        evs = [{"ph": "X", "pid": 1, "tid": 1, "name": "dot.1",
                "ts": 5.0, "args": {"hlo_op": "dot.1"}}]
        p = tmp_path / "zero.trace.json"
        p.write_text(json.dumps({"traceEvents": evs}))
        doc = trace_attrib.attribute(str(p))
        assert doc["attributed_frac"] is None
        assert "n/a" in trace_attrib.render(doc)
        assert trace_attrib.main([str(p)]) == 0

    def test_empty_dir_attributes_nothing(self, tmp_path):
        doc = trace_attrib.attribute(str(tmp_path))
        assert doc["categories"] == {} and not doc["attributed_ok"]
        assert doc["attributed_frac"] is None
        assert trace_attrib.main([str(tmp_path)]) == 2

    def test_main_writes_out_and_render(self, tmp_path, capsys):
        out = tmp_path / "attrib.json"
        txt = tmp_path / "attrib.txt"
        rc = trace_attrib.main([FIXTURE_DIR, "--out", str(out),
                                "--render", str(txt)])
        assert rc == 0
        doc = json.loads(out.read_text())
        assert doc["schema"] == trace_attrib.TRACE_ATTRIB_SCHEMA
        assert "matmul_conv_mxu" in txt.read_text()
        assert "attributed" in capsys.readouterr().out

    @pytest.mark.parametrize("name,cat", [
        ("convolution.12", "matmul_conv_mxu"),
        ("dot.8", "matmul_conv_mxu"),
        ("reduce-window.1", "reduce"),
        ("reduce_add_fusion", "reduce"),
        ("reduce-scatter.2", "collective"),
        ("all-gather.1", "collective"),
        ("copy-start.3", "infeed_outfeed_h2d"),
        ("outfeed", "infeed_outfeed_h2d"),
        ("dynamic-update-slice.4", "copy_reshape_transpose"),
        ("transpose.9", "copy_reshape_transpose"),
        ("loop_fusion", "elementwise"),
        ("fusion.17", "elementwise"),
        ("tanh.6", "elementwise"),
        ("threefry2x32", "elementwise"),
        # dtype casts are NOT MXU work: the conv rule must not eat
        # 'convert' (a bf16 trace is full of casts)
        ("convert.3", "elementwise"),
        ("convert_fusion", "elementwise"),
        ("bitcast-convert.1", "copy_reshape_transpose"),
        # canonical long-form HLO names (jnp.exp lowers to
        # 'exponential', % to 'remainder')
        ("exponential.1", "elementwise"),
        ("exponential-minus-one", "elementwise"),
        ("remainder.2", "elementwise"),
        ("atan2.1", "elementwise"),
        ("shift-left.4", "elementwise"),
        # control-flow shells are a named line item; unknown custom
        # kernels are not
        ("while.168", "control_flow"),
        ("conditional.2", "control_flow"),
        ("call.7", "control_flow"),
        ("custom-call.2", "other"),
    ])
    def test_category_rules(self, name, cat):
        assert trace_attrib.categorize(name) == cat


# -- end-to-end: CPU capture -> attribute -> report -------------------------
class TestEndToEndCapture:
    def test_cpu_capture_attributes_and_reports(self, tmp_path,
                                                capsys):
        """The acceptance bar: a real CPU-backend capture of the round
        program attributes >= 95% of device time into named
        categories, and ``fedtorch-tpu report --device`` renders it."""
        trainer = make_trainer()
        server, clients = trainer.init_state(jax.random.key(0))
        server, clients, _ = trainer.run_round(server, clients)  # warm
        cap_dir = str(tmp_path / "capture")
        with jax.profiler.trace(cap_dir):
            server, clients, _ = trainer.run_round(server, clients)
            # drained inside the window: a trace stopped earlier
            # records dispatch, not execution
            jax.block_until_ready(server.params)
        doc = trace_attrib.attribute(cap_dir)
        assert doc["device_events"] > 0
        assert doc["attributed_frac"] >= 0.95, doc
        assert doc["categories"]["matmul_conv_mxu"]["time_us"] > 0 \
            or doc["categories"]["elementwise"]["time_us"] > 0

        # program_costs beside the trace: report --device renders both
        programs, primary = trainer.lowered_cost_programs(server,
                                                          clients)
        capture_for(trainer, tmp_path / "capture").capture(
            programs, primary=primary)
        from fedtorch_tpu.cli import main
        assert main(["report", cap_dir, "--device"]) == 0
        out = capsys.readouterr().out
        assert "device-time attribution" in out
        assert "program costs" in out
        assert "attributed:" in out

    def test_report_device_without_metrics_or_traces_errors(
            self, tmp_path):
        from fedtorch_tpu.cli import main
        assert main(["report", str(tmp_path), "--device"]) == 2

    def test_report_device_surfaces_invalid_costs_file(self, tmp_path,
                                                       capsys):
        # a corrupt program_costs.json IS a (broken) capture: the
        # validation error must be shown, not "file not found"
        (tmp_path / "program_costs.json").write_text(
            json.dumps({"schema": "fedtorch_tpu.program_costs/v999"}))
        from fedtorch_tpu.cli import main
        assert main(["report", str(tmp_path), "--device"]) == 0
        out = capsys.readouterr().out
        assert "unreadable" in out and "v999" in out


class TestCliRunDeviceGauges:
    def test_mini_run_emits_costs_and_gauges(self, tmp_path):
        """run_experiment writes program_costs.json and every metrics
        row carries the HBM gauges (schema-valid) — and, on the CPU,
        no MFU: the device has no published peak."""
        from test_telemetry import _cli_cfg

        from fedtorch_tpu.cli import run_experiment
        from fedtorch_tpu.telemetry import iter_jsonl
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=3))
        doc = read_program_costs(run_dir)
        assert doc["primary"] == "round"
        assert {"round", "eval"} <= set(doc["programs"])
        assert doc["programs"]["eval"]["flops"] > 0
        rows = [r for r in iter_jsonl(os.path.join(run_dir,
                                                   "metrics.jsonl"))
                if "schema" not in r]
        assert len(rows) == 3
        assert doc["peak_tflops_per_chip"] is None
        for r in rows:
            validate_metrics_row(r)
            assert "model_flops_utilization" not in r
            assert r["hbm_program_peak_bytes"] > 0
            assert r["hbm_live_bytes"] > 0

    def test_telemetry_off_writes_no_costs(self, tmp_path):
        from test_telemetry import _cli_cfg

        from fedtorch_tpu.cli import run_experiment
        run_dir = str(tmp_path / "run")
        run_experiment(_cli_cfg(run_dir, rounds=2,
                                extra=("--telemetry", "off")))
        assert not os.path.exists(
            os.path.join(run_dir, "program_costs.json"))


class TestSaveAndEvalSpansInTheProfile:
    def test_new_span_names_lie_in_the_host_plane(self, tmp_path):
        """ISSUE 37's spans go through the one recorder, so its
        ``annotate`` hook puts each in the profile's host plane, on the
        device trace's clock, as often as ``trace.json`` holds it."""
        import glob
        import warnings

        from jax.profiler import ProfileData, ProfileOptions
        from test_telemetry import _cli_cfg

        from fedtorch_tpu.cli import run_experiment
        names = {"eval.batches", "eval.h2d", "eval.dispatch", "eval.fetch",
                 "checkpoint.layout", "checkpoint.digest",
                 "checkpoint.file_write.data",
                 "checkpoint.file_write.fsync",
                 "checkpoint.file_write.rename", "checkpoint.snapshot"}
        run_dir, prof_dir = str(tmp_path / "run"), str(tmp_path / "prof")
        opts = ProfileOptions()
        opts.python_tracer_level = 0     # annotations only: a small file
        jax.profiler.start_trace(prof_dir, profiler_options=opts)
        try:
            run_experiment(_cli_cfg(run_dir, rounds=2))
        finally:
            jax.profiler.stop_trace()
        doc = json.load(open(os.path.join(run_dir, "trace.json")))
        recorded = sorted(e["name"] for e in doc["traceEvents"]
                          if e.get("ph") == "X" and e["name"] in names)
        prof = ProfileData.from_file(glob.glob(os.path.join(
            prof_dir, "plugins", "profile", "*", "*.xplane.pb"))[0])
        # see test_telemetry: jaxlib's stats iterator warns when made
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            annotated = sorted(
                ev.name for plane in prof.planes
                if plane.name.startswith("/host:")
                for line in plane.lines for ev in line.events
                if ev.name in names)
        assert set(recorded) == names
        assert annotated == recorded
