"""One run of one cell: the launcher's own loop (``cli.run_experiment``)
driven from the cell's data files, timed through its round callback,
ended by its documented drain, reduced to the contract's result line.

Everything specific to a configuration, a traffic mix or a per-layer
metric is found by name under ``benchmark/``; this file knows none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import math
import os
import shutil
import signal
import statistics
import sys
import time
from typing import Dict, List, Optional

from . import correct as correct_mod
from . import trace_reduce, window

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
GIB = float(1 << 30)
MAX_ROUNDS = 20000      # the launcher's round budget: no window reaches it
WARM_ROUNDS = 10        # set-up holds this many rounds, in whole cycles
KEEP_SEEDS = 8          # seeded data sets kept under .cache/data


_T0 = time.time()


def log(msg: str) -> None:
    """A line of the run's own, stamped with the seconds since start."""
    head, _, rest = msg.partition(": ")
    print(f"{head}: [{time.time() - _T0:7.1f}s] {rest}", flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


# -- the cell, from its data files ---------------------------------------

def load_cell(workload: str) -> dict:
    """The workload's entry of ``BENCHMARK.json`` with its configuration
    and traffic files, all found by name."""
    bench = load_json(os.path.join(REPO, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; BENCHMARK.json "
                         f"has {sorted(cells)}")
    cell = dict(cells[workload])
    cell["config_file"] = load_json(os.path.join(
        BENCH, "configs", cell["config"] + ".json"))
    cell["traffic_file"] = load_json(os.path.join(
        BENCH, "traffic", cell["traffic"] + ".json"))
    cell["bench"] = bench
    return cell


def metrics_for(cell: dict, group: str) -> List[dict]:
    """The metrics of ``group`` ('end_to_end' | 'per_layer') that this
    cell reports: those with no ``workloads`` key, or that list it."""
    return [m for m in cell["bench"][group]
            if "workloads" not in m or cell["name"] in m["workloads"]]


def launcher_argv(cell: dict, seed: int, data_dir: str,
                  run_dir: str) -> List[str]:
    """The launcher's long flags from the configuration's and the
    traffic's ``launcher`` tables (the traffic wins a shared key), plus
    what only a run knows: its directories, its seed, its device count
    and a round budget that no window can reach. An unknown key fails
    in the launcher's parser as an unknown flag would."""
    from fedtorch_tpu.cli import build_parser

    flags = dict(cell["config_file"]["launcher"])
    flags.update(cell["traffic_file"]["launcher"])
    flags.update(data_dir=data_dir, run_dir=run_dir,
                 # jax.random.key and numpy's RandomState take 31/32 bits
                 manual_seed=seed & 0x7FFFFFFF,
                 num_comms=MAX_ROUNDS,
                 num_devices=cell["chips"])
    actions = build_parser()._option_string_actions
    argv: List[str] = []
    for key, value in flags.items():
        act = actions.get("--" + key)
        if act is not None and act.nargs == 0:     # store_true flag
            if value:
                argv.append("--" + key)
            continue
        argv += ["--" + key, str(value)]
    return argv


def load_by_name(kind: str, name: str):
    """``benchmark/<kind>/<name>.py`` as a module, by file name."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    if name.isidentifier():
        return importlib.import_module(f"benchmark.{kind}.{name}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seeded_data(dataset: str, seed: int, sizes: dict) -> str:
    """The seed's data files in the dataset's own format, written by
    ``benchmark/datagen/<dataset>.py`` or found as an earlier run of
    the same seed and sizes left them under ``.cache/data``. The newest
    ``KEEP_SEEDS`` sets are kept."""
    store = os.path.join(BENCH, ".cache", "data")
    name = "-".join([dataset, str(seed)]
                    + [str(v) for _, v in sorted(sizes.items())])
    root = os.path.join(store, name)
    marker = os.path.join(root, "written.json")
    if not os.path.exists(marker):
        tmp = root + ".tmp"
        for d in (root, tmp):
            shutil.rmtree(d, ignore_errors=True)
        os.makedirs(tmp)
        data_dir = load_by_name("datagen", dataset).write(tmp, seed,
                                                          **sizes)
        with open(os.path.join(tmp, "written.json"), "w") as f:
            json.dump({"data_dir": os.path.relpath(data_dir, tmp)}, f)
        os.rename(tmp, root)
    os.utime(root)
    sets = sorted((os.path.join(store, d) for d in os.listdir(store)),
                  key=os.path.getmtime)
    for old in sets[:-KEEP_SEEDS]:
        shutil.rmtree(old, ignore_errors=True)
    return os.path.normpath(os.path.join(
        root, load_json(marker)["data_dir"]))


# -- the device ----------------------------------------------------------

def require_chips(chips: int) -> None:
    """Exit non-zero, before anything compiles, unless JAX shows the
    cell's TPU chips. No fallback to the CPU."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < chips:
        sys.stderr.write(
            f"benchmark: needs {chips} TPU chip(s), found "
            f"{len(devs)} x {devs[0].platform} ({devs[0].device_kind}); "
            "no CPU fallback\n")
        raise SystemExit(3)


def device_info() -> dict:
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def device_memory(stat: str) -> int:
    """``stat`` of the allocator of the fullest device."""
    import jax

    return int(max((d.memory_stats() or {}).get(stat, 0)
                   for d in jax.devices()))


# -- the loop's callback -------------------------------------------------

class Loop:
    """State of one run, filled by the launcher's ``round_callback``.

    Rounds 0-2 are the rounds ``correct`` follows: the server's
    parameters are copied to the host after the first and the last
    (``compare`` reads no other). The first whole
    cycles that hold ``WARM_ROUNDS`` rounds are set-up; the window is
    the whole cycles after them; at the callback that closes it the
    loop reads the device's memory peak and asks the launcher for its
    drain (SIGUSR1), so the run ends through the launcher's normal exit.
    A traced run's window goes on, past its plain length, for the whole
    cycles that hold ``WARM_ROUNDS`` rounds, and the profile covers them.
    """

    CHECK_ROUNDS = 3

    def __init__(self, cell: dict, seconds: float, trace: bool,
                 trace_dir: str, t_start: float):
        tf = cell["traffic_file"]
        self.eval_freq = int(tf["launcher"].get("eval_freq", 1))
        self.warm = window.warmup_rounds(self.eval_freq, WARM_ROUNDS)
        self.algorithm = tf["algorithm"]
        self.stated = cell["config_file"]["precision"]["parameters"]
        self.late: dict = {}
        self.trace_cycles = self.warm // self.eval_freq if trace else 0
        self.trace_dir = trace_dir
        self.seconds = float(seconds)
        self.t_start = t_start
        self.stamps: Dict[int, float] = {}
        self.lost = 0.0     # seconds spent inside this callback so far
        self.params_after: Dict[int, dict] = {}
        self.copy_s = 0.0   # of ``lost``: the parameters' copies to the host
        self.trainer = None
        self.open_round: Optional[int] = None     # last warm-up round
        self.close_round: Optional[int] = None
        self.setup_s: Optional[float] = None
        self.peak_bytes = 0
        self.trace_rounds: Optional[tuple] = None  # (first, last)
        self.trace_unix: Optional[tuple] = None
        self._tracing = False
        self._sentinel = None
        self.traces_in_window: Dict[str, int] = {}

    def __call__(self, r, trainer, server, clients, metrics) -> None:
        """Stamp the round on the loop's clock, which stands still while
        the benchmark's own work in this callback runs (host copies for
        ``correct``, the profiler's start and stop, the memory reading):
        that time belongs to no round and to no window."""
        t_in = time.perf_counter()
        self.stamps[r] = t_in - self.lost
        try:
            self._on_round(r, trainer, server, clients)
        finally:
            self.lost += time.perf_counter() - t_in

    def _copy(self, params):
        """The server's parameters on the host, for ``correct``."""
        import jax

        t0 = time.perf_counter()
        out = jax.device_get(params)
        self.copy_s += time.perf_counter() - t0
        return out

    def _on_round(self, r, trainer, server, clients) -> None:
        import jax

        if self.close_round is not None:
            if r == self.close_round + 1:
                # the drain's extra round: the late round of ``correct``
                self.late["after"] = self._copy(server.params)
            return
        self.trainer = trainer
        if r in (0, self.CHECK_ROUNDS - 1):
            self.params_after[r] = self._copy(server.params)
        if r == self.warm - 1:
            self.open_round = r
            self.setup_s = time.time() - self.t_start
            from fedtorch_tpu.utils.tracing import RecompilationSentinel
            self._sentinel = RecompilationSentinel()
            self._sentinel.__enter__()
            return
        if self.open_round is None:
            return
        if not window.closes_window(r, self.stamps[r],
                                    self.stamps[self.open_round],
                                    self.seconds, self.eval_freq):
            return
        if self.trace_cycles and not self._tracing \
                and self.trace_rounds is None:
            # a traced run's window goes on for the traced stretch: the
            # cycles before it run as a plain run's do (after a profile
            # the launcher's evaluation and checkpoint read faster for
            # the rest of the run, PERF.md section 7)
            jax.profiler.start_trace(self.trace_dir)
            self._tracing = True
            self.trace_rounds = (r + 1, None)
            self.trace_unix = (time.time(), None)
            return
        if self._tracing:
            if r - self.trace_rounds[0] + 1 < self.trace_cycles \
                    * self.eval_freq:
                return
            self.trace_unix = (self.trace_unix[0], time.time())
            jax.profiler.stop_trace()
            self._tracing = False
            self.trace_rounds = (self.trace_rounds[0], r)
        self.close_round = r
        self.peak_bytes = device_memory("peak_bytes_in_use")
        self._sentinel.__exit__(None, None, None)
        self.traces_in_window = dict(self._sentinel.counts)
        self.late = {"round": r + 1,
                     "dtype_mismatch": correct_mod.dtype_mismatch(
                         (server.params, clients), self.stated),
                     "before": self._copy(server.params),
                     "state": self._client_state(server, clients,
                                                 r + 1)}
        os.kill(os.getpid(), signal.SIGUSR1)   # the documented drain

    def _client_state(self, server, clients, round_idx):
        """The next round's cohort's state, where the algorithm's
        reference names one (``STATE_FROM_AUX`` in its file)."""
        import jax
        import numpy as np

        from . import inputs

        key = getattr(load_by_name("reference", self.algorithm),
                      "STATE_FROM_AUX", None)
        if key is None:
            return None
        cohort = inputs.cohort_of(self.trainer, server.rng, round_idx)
        return {int(c): jax.tree.map(lambda x: np.asarray(x[int(c)]),
                                     clients.aux[key]) for c in cohort}


# -- what the launcher wrote ---------------------------------------------

def read_rows(run_dir: str) -> List[dict]:
    rows = []
    with open(os.path.join(run_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if "round" in rec and "round_s" in rec:
                rows.append(rec)
    return rows


def read_spans(run_dir: str) -> dict:
    """``{"origin_unix": s, "spans": [(name, start_s, dur_s, args)]}``
    from the launcher's host trace; starts are seconds after origin."""
    path = os.path.join(run_dir, "trace.json")
    if not os.path.exists(path):
        return {"origin_unix": None, "spans": []}
    doc = load_json(path)
    spans = [(e["name"], e["ts"] / 1e6, e["dur"] / 1e6, e.get("args") or {})
             for e in doc["traceEvents"] if e.get("ph") == "X"]
    return {"origin_unix": doc.get("otherData", {}).get("origin_unix"),
            "spans": spans}


# -- one run ---------------------------------------------------------------

def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             require_chip: bool = True, t_start: Optional[float] = None,
             overrides: Optional[dict] = None,
             keep_check: bool = False) -> dict:
    """Drive the cell and return the result line as a dict.
    ``overrides`` (tests only) replaces keys of the configuration's
    ``datagen`` / ``launcher`` tables to reach a size a CPU holds."""
    t_start = time.time() if t_start is None else t_start
    cell = load_cell(workload)
    if overrides:
        for table, repl in overrides.items():
            if table in ("datagen", "launcher"):
                cell["config_file"][table].update(repl)
            elif table == "traffic":
                cell["traffic_file"].update(repl)
    if require_chip:
        require_chips(cell["chips"])
    from fedtorch_tpu.cli import args_to_config, build_parser, \
        run_experiment

    work = os.path.join(BENCH, ".cache", workload)
    run_dir, trace_dir = (os.path.join(work, d) for d in ("run", "trace"))
    for d in (run_dir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(work, exist_ok=True)

    cfgf = cell["config_file"]
    t0 = time.time()
    data_dir = seeded_data(cfgf["dataset"], seed, cfgf["datagen"])
    datagen_s = time.time() - t0
    log(f"benchmark: data for seed {seed} in {datagen_s:.1f}s")

    argv = launcher_argv(cell, seed, data_dir, run_dir)
    log("benchmark: launcher flags: " + " ".join(argv))
    cfg = args_to_config(build_parser().parse_args(argv))
    loop = Loop(cell, seconds, trace, trace_dir, t_start)
    results = run_experiment(cfg, round_callback=loop)
    if loop.close_round is None:
        raise RuntimeError(f"the round budget ({MAX_ROUNDS}) ran out "
                           "before the window closed")
    if not results.get("preempted"):
        raise RuntimeError("the launcher did not leave through its drain")

    rows = read_rows(run_dir)
    spans = read_spans(run_dir)
    first, last = loop.open_round + 1, loop.close_round
    wrows = window.window_rows(rows, first, last)
    window_s = loop.stamps[last] - loop.stamps[loop.open_round]
    per_round = int(loop.trainer.k_online) * int(loop.trainer.local_steps) \
        * int(loop.trainer.batch_size)
    # the benchmark's own clock: callback to callback over the
    # iterations that hold a train round and nothing else
    round_s = window.train_iteration_walls(loop.stamps, first, last,
                                           loop.eval_freq)
    failed = sum(1 for r in wrows
                 if not math.isfinite(r["loss"]) or r.get("dropped", 0)
                 or r.get("rejected", 0) or r.get("sup_retries", 0))

    ctx = {
        "cell": cell, "rows": wrows, "all_rows": rows,
        "stamps": loop.stamps, "spans": spans,
        "window": {"first": first, "last": last, "seconds": window_s,
                   "warmup_rounds": loop.warm},
        "traces_in_window": loop.traces_in_window,
        "samples_per_round": per_round, "trace": None,
        "device": device_info(),
    }
    loop_rate = window.samples_per_s_chip(
        len(wrows), per_round, window_s, cell["chips"])
    end_to_end = {
        "round_s_p50": statistics.median(round_s) if round_s else None,
        "peak_hbm_gib": loop.peak_bytes / GIB,
        "setup_s": loop.setup_s,
    }
    log(f"benchmark: window rounds {first}..{last} ({len(wrows)} rounds, "
        f"{window_s:.3f}s); set-up {loop.setup_s:.1f}s of which data "
        f"files {datagen_s:.1f}s; {len(round_s)} train iterations, "
        "quartiles " + str([round(q, 5) for q in statistics.quantiles(
            round_s, n=4)] if len(round_s) > 1 else round_s)
        + "; cycle walls " + str([round(c, 3) for c in window.cycle_walls(
            loop.stamps, loop.open_round, last, loop.eval_freq)]))
    log(f"benchmark: window {loop_rate:.1f} samples/s/chip, checkpoint_s "
        + str([round(r["checkpoint_s"], 2) for r in wrows
               if "checkpoint_s" in r][:12])
        + " eval_s " + str([round(r["eval_s"], 2) for r in wrows
                            if "eval_s" in r][:12]))

    device = dict(ctx["device"], memory_peak_bytes=loop.peak_bytes)
    extra: dict = {}
    if trace:
        reduced = reduce_trace(loop, trace_dir, spans)
        ctx["trace"] = reduced
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        extra["breakdown"] = reduced["breakdown"]
        metrics = {}
        for m in metrics_for(cell, "per_layer"):
            value = load_by_name("layer_metrics", m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": end_to_end[m["name"]],
                               "unit": m["unit"]}
                   for m in metrics_for(cell, "end_to_end")
                   if end_to_end[m["name"]] is not None}

    # the reference runs once the launcher has returned and nothing of
    # the program is left on the device (its state, and the trainer
    # with its store once the reference's inputs are rebuilt): the
    # memory peak above stays the program's
    trainer, loop.trainer = loop.trainer, None
    t0 = time.perf_counter()
    case = correct_mod.gather_case(cfg, trainer, loop.late,
                                   loop.CHECK_ROUNDS)
    inputs_s = time.perf_counter() - t0
    del trainer, results
    log(f"benchmark: {device_memory('bytes_in_use') / GIB:.3f} GiB in use "
        "on the device as the reference starts")
    copies = len(loop.params_after) + 2
    verdict = correct_mod.check(cell, cfg, case, loop.params_after,
                                loop.late, rows, keep=keep_check)
    log(f"benchmark: correct cost: the callback's {copies} copies of "
        f"the parameters {loop.copy_s:.2f}s, the reference's inputs "
        f"{inputs_s:.2f}s, its {loop.CHECK_ROUNDS + 1} rounds "
        f"{verdict['seconds']['reference']:.2f}s, the comparison "
        f"{verdict['seconds']['compare']:.2f}s")
    for line in verdict["lines"]:
        print("benchmark: correct: " + line, flush=True)
    for d in (run_dir, trace_dir):
        shutil.rmtree(d, ignore_errors=True)

    out = {"correct": verdict["correct"], "attempted": len(wrows),
           "failed": failed, "metrics": metrics, "device": device, **extra}
    if keep_check:   # the control script reads the case and both sides
        out["_check"] = verdict
    out["seed"] = seed
    out["workload"] = workload
    out["window"] = {"rounds": len(wrows), "seconds": window_s,
                     "first_round": first, "last_round": last}
    # each number compared beside its limit: the result's last key and
    # the run's last lines on standard error
    out["compared"] = verdict["compared"]
    for name, c in out["compared"].items():
        sys.stderr.write(f"benchmark: compared {name} = {c['value']} "
                         f"limit {c['limit']}\n")
    sys.stderr.flush()
    return out


def reduce_trace(loop: Loop, trace_dir: str, spans: dict) -> dict:
    path = trace_reduce.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError("the profiler wrote no trace")
    out_dir = os.path.join(BENCH, "out")
    os.makedirs(out_dir, exist_ok=True)
    prof = trace_reduce.read_profile(path)
    with open(os.path.join(out_dir, "trace_planes.txt"), "w") as f:
        f.write("\n".join(trace_reduce.describe(prof)) + "\n")
    events = trace_reduce.load_device_events(prof)
    if not events:
        raise RuntimeError("no device operation in the trace")
    # the traced stretch on the trace's own clock: first to last device
    # event, which the callbacks that started and stopped it enclose
    w0 = min(e["start"] for e in events)
    w1 = max(e["start"] + e["dur"] for e in events)
    # the traced stretch is what the host held open between the two
    # callbacks; the trace's clock is tied to it at the first device
    # event, which the round dispatched right after the start produces
    host_window_s = loop.trace_unix[1] - loop.trace_unix[0]
    w1 = max(w1, w0 + host_window_s)
    reduced = trace_reduce.reduce_events(events, (w0, w1))
    first, last = loop.trace_rounds
    reduced["rounds"] = last - first + 1
    modules = trace_reduce.module_busy(events, trace_reduce.load_device_events(
        prof, line_name=trace_reduce.MODULES_LINE))
    reduced["modules"] = modules
    # the round program: the module run once per traced round that
    # holds most of the device's time
    per_round = {n: m for n, m in modules.items()
                 if abs(m["runs"] - reduced["rounds"]) < 0.5}
    reduced["round_module"] = max(
        per_round.values(), key=lambda m: m["busy_s"]) if per_round else None
    log("benchmark: traced modules: " + json.dumps(
        {n: [m["runs"], round(m["busy_s"], 4)] for n, m in modules.items()}))
    # host spans moved onto the trace's clock by their common window:
    # the traced stretch starts at the callback that opened it
    host = []
    if spans["origin_unix"] is not None:
        shift = w0 - (loop.trace_unix[0] - spans["origin_unix"])
        host = [(n, s + shift, s + d + shift)
                for n, s, d, _ in spans["spans"]
                if n in ("round", "eval", "checkpoint", "scalar_fetch")
                and s + d + shift > w0 and s + shift < w1]
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:10]
    cats = sorted(reduced["category_s"].items(), key=lambda kv: -kv[1])
    log("benchmark: traced categories: " + json.dumps(
        {c: round(v, 4) for c, v in cats}))
    reduced["breakdown"] = {
        "device_ops": [[n, s] for n, s in ops],
        "idle_gaps": trace_reduce.label_gaps(reduced["gaps"], host),
    }
    reduced.pop("gaps")
    return reduced
