"""Compiled-program cost capture (device-side observability, pillar 1
of docs/observability.md "Device-side").

The host spans answer "where did host wall-clock go"; this module
answers the device half's first question — "what does the compiled
round actually cost" — straight from XLA's own accounting:
``Compiled.cost_analysis()`` (FLOPs, transcendentals, bytes accessed)
and ``Compiled.memory_analysis()`` (argument/output/temp buffer sizes,
whose sum is the program's peak device-memory watermark).

Contract (pinned in tests/test_device_observability.py):

* **Zero effect on the traced program.** Cost capture AOT-lowers
  UNINSTRUMENTED twins of the run's jitted programs (the trainers'
  ``lowered_cost_programs``) — the live jit caches are untouched, the
  recompilation sentinel sees zero extra trace events, and the twin's
  HLO is byte-identical to the live program's. Its persistent-cache
  KEY is not, on the TPU (jax 0.9.0, PR 21 chip run): a cold run
  compiles the round a second time for the twin (33 s + 29 s on the
  ResNet-20 north star); a later process hits both entries.
* **Graceful None.** A backend that doesn't report a statistic yields
  ``None`` for that field, never an exception: a lost FLOPs count must
  not lose the run (same rule the bench scripts always had).
* **Emitted once.** ``ProgramCostCapture`` writes a schema-versioned
  ``program_costs.json`` into the run dir at the first round and then
  only serves host-side gauges (``model_flops_utilization``, the HBM
  watermark pair) to the metrics row — zero added device syncs.

Import cost: stdlib-only at module level (the telemetry package's
no-jax rule); every jax touch is inside a function, so the report tool
and external monitors can import the schema half backend-free.
"""
from __future__ import annotations

import json
import os
import time
from typing import Dict, Optional, Tuple

PROGRAM_COSTS_SCHEMA = "fedtorch_tpu.program_costs/v1"

# the ``flops_source`` of a program_costs.json record
FLOPS_XLA = "xla_cost_analysis"

# analytic accounting behind program_costs.json's ``analytic`` block:
# resnet20-cifar forward = 40.8e6 MACs/image (stem 0.44M + 3 stages x
# ~13-14M + fc; the 41M figure in the ResNet paper), training step ~=
# 3x forward, 2 FLOPs/MAC
ANALYTIC_MACS_PER_IMAGE = {"resnet20": 40.8e6}
_TRAIN_STEP_OVER_FWD = 3 * 2  # bwd ~= 2x fwd, 2 FLOPs per MAC

# Published per-chip peaks in TFLOP/s, keyed by the device's
# ``device_kind`` (``jax.devices()[0].device_kind``) and then by the
# compute dtype. A (kind, dtype) that is not in the table has NO peak:
# the MFU gauges are omitted, never computed against another part's
# number. Add a part here with its source.
PEAK_TFLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip
    "TPU v5 lite": {"bfloat16": 197.0},
}


def analytic_train_flops_per_image(arch: str) -> Optional[float]:
    """Hand-derived training FLOPs per image for the archs we carry a
    constant for (currently the north-star resnet20); None elsewhere —
    callers must report timing without an MFU rather than invent one."""
    macs = ANALYTIC_MACS_PER_IMAGE.get(arch)
    return _TRAIN_STEP_OVER_FWD * macs if macs is not None else None


def resolve_peak_tflops(device_kind: Optional[str],
                        dtype: str) -> Tuple[Optional[float], str]:
    """(peak TFLOPs/chip or None, source string) from
    :data:`PEAK_TFLOPS`. ``None`` for a device kind (CPU included) or
    dtype the table does not list — the caller then reports no MFU.
    The source string is recorded next to every number derived from
    the peak."""
    peak = PEAK_TFLOPS.get(device_kind or "", {}).get(dtype)
    if peak is None:
        return None, f"none:{device_kind}:{dtype}"
    return peak, f"table:{device_kind}:{dtype}"


# -- XLA cost extraction ------------------------------------------------


def cost_summary(compiled) -> Dict[str, Optional[float]]:
    """Extract the catalogued statistics from a ``jax.stages.Compiled``
    — ``cost_analysis()`` FLOPs/transcendentals/bytes-accessed and
    ``memory_analysis()`` buffer sizes. Every field is ``None`` when
    the backend reports nothing for it (graceful-None contract);
    ``compiled=None`` gives the all-None summary."""
    out: Dict[str, Optional[float]] = {
        "flops": None, "transcendentals": None, "bytes_accessed": None,
        "argument_bytes": None, "output_bytes": None, "temp_bytes": None,
        "generated_code_bytes": None, "alias_bytes": None,
        "peak_hbm_bytes": None,
    }
    if compiled is None:
        return out
    ca = compiled.cost_analysis()
    if ca:
        fl = float(ca.get("flops", 0.0))
        out["flops"] = fl if fl > 0 else None
        tr = float(ca.get("transcendentals", 0.0))
        out["transcendentals"] = tr if tr > 0 else None
        ba = float(ca.get("bytes accessed", 0.0))
        out["bytes_accessed"] = ba if ba > 0 else None
    ma = compiled.memory_analysis()
    if ma is not None:
        arg = float(ma.argument_size_in_bytes)
        outb = float(ma.output_size_in_bytes)
        tmp = float(ma.temp_size_in_bytes)
        gen = float(ma.generated_code_size_in_bytes)
        ali = float(ma.alias_size_in_bytes)
        out.update(argument_bytes=arg, output_bytes=outb,
                   temp_bytes=tmp, generated_code_bytes=gen,
                   alias_bytes=ali)
        # the watermark: everything resident while the program runs
        # (donated/aliased output pages reuse argument pages, so
        # they are not double-counted)
        out["peak_hbm_bytes"] = arg + outb + tmp + gen - ali
    return out


def lowered_cost(lowered) -> Dict[str, Optional[float]]:
    """Compile a ``jax.stages.Lowered`` and summarize it; any failure
    collapses to the all-None summary plus an ``error`` note (a cost
    capture must never take down its caller)."""
    try:
        summary = cost_summary(lowered.compile())
    except Exception as e:
        summary = cost_summary(None)
        summary["error"] = f"{type(e).__name__}: {e}"[:200]
    summary["flops_source"] = FLOPS_XLA if summary.get("flops") else None
    return summary


# -- the program_costs.json document ------------------------------------

# field catalogs, mirroring telemetry.schema's metrics-row contract:
# validate_program_costs rejects uncataloged fields so the document
# cannot silently drift from what docs/observability.md describes
PROGRAM_FIELDS = {
    "flops": "executed FLOPs (XLA cost analysis)",
    "transcendentals": "transcendental op count",
    "bytes_accessed": "bytes read+written by the program",
    "argument_bytes": "input buffer bytes",
    "output_bytes": "output buffer bytes",
    "temp_bytes": "intermediate buffer bytes",
    "generated_code_bytes": "executable code bytes",
    "alias_bytes": "donated input bytes reused as outputs",
    "peak_hbm_bytes": "arg+out+temp+code-alias device watermark",
    "flops_source": "xla_cost_analysis or None",
    "error": "capture failure note (program still listed)",
}

_TOP_REQUIRED = ("schema", "created_unix", "backend", "num_devices",
                 "compute_dtype", "peak_tflops_per_chip", "peak_source",
                 "programs")
_TOP_OPTIONAL = ("run", "analytic", "primary")


def validate_program_costs(doc: Dict) -> None:
    """Raise ``ValueError`` when ``doc`` violates the v1 contract —
    the program_costs twin of ``validate_metrics_row``."""
    if doc.get("schema") != PROGRAM_COSTS_SCHEMA:
        raise ValueError(
            f"program_costs schema {doc.get('schema')!r} != "
            f"{PROGRAM_COSTS_SCHEMA!r}")
    for key in _TOP_REQUIRED:
        if key not in doc:
            raise ValueError(f"program_costs missing required {key!r}")
    unknown = [k for k in doc
               if k not in _TOP_REQUIRED and k not in _TOP_OPTIONAL]
    if unknown:
        raise ValueError(
            f"program_costs carries uncataloged top-level fields "
            f"{unknown!r}")
    programs = doc["programs"]
    if not isinstance(programs, dict) or not programs:
        raise ValueError("program_costs 'programs' must be a non-empty "
                         "dict of program-name -> cost summary")
    for name, rec in programs.items():
        if not isinstance(rec, dict):
            raise ValueError(f"program {name!r} record must be a dict")
        bad = [k for k in rec if k not in PROGRAM_FIELDS]
        if bad:
            raise ValueError(
                f"program {name!r} carries uncataloged fields {bad!r} "
                "— add them to telemetry.costs.PROGRAM_FIELDS (the "
                "catalog docs/observability.md renders)")
        for k, v in rec.items():
            if k in ("flops_source", "error"):
                if v is not None and not isinstance(v, str):
                    raise ValueError(
                        f"program {name!r} field {k!r} must be str or "
                        f"None, got {type(v).__name__}")
            elif v is not None and (isinstance(v, bool)
                                    or not isinstance(v, (int, float))):
                raise ValueError(
                    f"program {name!r} field {k!r} must be numeric or "
                    f"None, got {type(v).__name__} ({v!r})")


def program_costs_path(run_dir: str) -> str:
    return os.path.join(run_dir, "program_costs.json")


def read_program_costs(run_dir: str) -> Optional[Dict]:
    """The validated document, or None when the run never captured."""
    path = program_costs_path(run_dir)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        doc = json.load(f)
    validate_program_costs(doc)
    return doc


class ProgramCostCapture:
    """Once-per-run cost capture + the per-round device gauges.

    Built by the CLI loop (process 0, telemetry on); :meth:`capture`
    runs once right after the first round — the live program is
    compiled (the twins' own compiles are cache hits only from the
    second process on, see the module docstring) — and writes
    ``program_costs.json`` atomically. :meth:`round_gauges` then turns
    each round's wall-clock into the measured-MFU and HBM-watermark
    row fields from host state alone. Attempt-once semantics: a failed
    capture is logged and never retried (and never raises — cost
    accounting must not take down training)."""

    def __init__(self, run_dir: str, *, compute_dtype: str = "float32",
                 arch: Optional[str] = None,
                 batch_size: Optional[int] = None,
                 local_steps: Optional[int] = None,
                 k_online: Optional[int] = None,
                 num_devices: int = 1, backend: Optional[str] = None,
                 device_kind: Optional[str] = None,
                 run_meta: Optional[Dict] = None, log=None):
        self.run_dir = run_dir
        self.compute_dtype = compute_dtype
        self.arch = arch
        self.batch_size = batch_size
        self.local_steps = local_steps
        self.k_online = k_online
        self.num_devices = max(int(num_devices), 1)
        self.backend = backend
        self.run_meta = run_meta
        self.log = log or (lambda *_: None)
        self.peak_tflops, self.peak_source = resolve_peak_tflops(
            device_kind, compute_dtype)
        self.captured = False
        self.doc: Optional[Dict] = None
        self._primary: Optional[Dict] = None
        self._live_cache: Optional[float] = None
        self._live_cost_s = 0.0
        self._rows_since_live = 0

    # -- the one-shot capture ------------------------------------------
    def load_existing(self) -> bool:
        """Adopt a previous attempt's ``program_costs.json`` instead
        of re-capturing. Elastic restarts reuse the run dir, and even
        a cache hit costs a trace, a lowering and an executable load
        per twin (seconds on the chip); the gauges resume from the
        recorded primary without touching the backend."""
        try:
            doc = read_program_costs(self.run_dir)
        except (ValueError, OSError, json.JSONDecodeError):
            return False
        if doc is None:
            return False
        # any schema-valid document is adopted, even one without a
        # usable primary (gauges stay off then): half-adopting and
        # re-capturing would pay exactly the recompile this path exists
        # to avoid
        self.captured = True
        self.doc = doc
        self._primary = doc["programs"].get(doc.get("primary"))
        self.log("cost capture: adopted existing program_costs.json "
                 f"(primary {doc.get('primary')!r}"
                 + ("" if self._primary is not None
                    else " — not found, device gauges off") + ")")
        return True

    def _analytic_block(self) -> Optional[Dict]:
        """The analytic roofline for the active config: hand-derived
        per-image training FLOPs scaled to one round (k clients x K
        local steps x batch B) — the yardstick the XLA number is read
        against (docs/performance.md 'MFU roofline')."""
        if self.arch is None:
            return None
        per_image = analytic_train_flops_per_image(self.arch)
        block: Dict = {"arch": self.arch,
                       "train_flops_per_image": per_image}
        if per_image is not None and self.batch_size \
                and self.local_steps and self.k_online:
            block["round_flops"] = (per_image * self.batch_size
                                    * self.local_steps * self.k_online)
        return block

    def capture(self, programs: Dict, primary: Optional[str] = None
                ) -> Optional[Dict]:
        """Compile + summarize each ``{name: jax.stages.Lowered}`` and
        write ``program_costs.json``. ``primary`` names the program
        whose FLOPs/watermark feed the per-round gauges (default: the
        first entry). Absorbs every failure."""
        self.captured = True  # attempt-once, success or not
        try:
            costs = {name: lowered_cost(lowered)
                     for name, lowered in programs.items()}
            if not costs:
                self.log("cost capture: no programs offered; skipped")
                return None
            if primary is None:
                primary = next(iter(costs))
            doc = {
                "schema": PROGRAM_COSTS_SCHEMA,
                "created_unix": time.time(),
                "backend": self.backend,
                "num_devices": self.num_devices,
                "compute_dtype": self.compute_dtype,
                "peak_tflops_per_chip": self.peak_tflops,
                "peak_source": self.peak_source,
                "primary": primary,
                "programs": costs,
            }
            analytic = self._analytic_block()
            if analytic is not None:
                doc["analytic"] = analytic
            if self.run_meta:
                doc["run"] = self.run_meta
            validate_program_costs(doc)
            self._write(doc)
            self.doc = doc
            self._primary = costs.get(primary)
            fl = (self._primary or {}).get("flops")
            self.log(f"cost capture: {len(costs)} program(s) -> "
                     f"{program_costs_path(self.run_dir)} "
                     f"(primary {primary!r}, flops="
                     f"{fl if fl is not None else 'unreported'})")
            return doc
        except Exception as e:
            self.log(f"cost capture failed ({type(e).__name__}: "
                     f"{str(e)[:160]}); training continues without "
                     "device gauges")
            return None

    def _write(self, doc: Dict) -> None:
        """Atomic replace, like health.json: a reader never sees a
        torn document."""
        path = program_costs_path(self.run_dir)
        tmp = path + ".tmp"
        os.makedirs(self.run_dir, exist_ok=True)
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
        os.replace(tmp, path)

    # -- per-round gauges ----------------------------------------------
    def round_gauges(self, round_s: float) -> Dict[str, float]:
        """The metrics-row fields this pillar adds, all host-side:

        * ``model_flops_utilization`` — primary-program FLOPs /
          (round wall x peak x chips), the measured-MFU gauge (only
          when the device has a published peak, :data:`PEAK_TFLOPS`);
        * ``hbm_program_peak_bytes`` — the compiled program's static
          device-memory watermark (memory_analysis);
        * ``hbm_live_bytes`` — live ``jax.Array`` bytes
          (``utils.tracing.live_buffer_summary`` — metadata walk, no
          transfer), the dynamic half of the watermark pair.

        Empty until :meth:`capture` succeeded, so rows stay stable."""
        if self._primary is None:
            return {}
        out: Dict[str, float] = {}
        flops = self._primary.get("flops")
        if flops and round_s > 0 and self.peak_tflops is not None:
            out["model_flops_utilization"] = flops / (
                round_s * self.peak_tflops * 1e12 * self.num_devices)
            # the round-wall critical path's device side
            # (telemetry/critical_path.py): the FLOPs-at-peak floor of
            # device-busy time, and the wall share it does NOT explain
            # — host phases + dispatch gap + sub-peak MXU occupancy
            floor = flops / (self.peak_tflops * 1e12 * self.num_devices)
            out["round_device_min_s"] = floor
            out["round_host_frac"] = min(
                max(1.0 - floor / round_s, 0.0), 1.0)
        peak = self._primary.get("peak_hbm_bytes")
        if peak is not None:
            out["hbm_program_peak_bytes"] = float(peak)
        live = self._live_bytes(round_s)
        if live is not None:
            out["hbm_live_bytes"] = live
        return out

    _LIVE_REFRESH_ROWS = 25
    _LIVE_BUDGET_FRAC = 0.002

    def _live_bytes(self, round_s: float) -> Optional[float]:
        """The live-array watermark, adaptively sampled: the walk is
        O(live arrays) host work (~3 ms at ~90 arrays), which would
        dominate millisecond rounds and break the <=1% telemetry bar —
        so it refreshes when its own measured cost fits inside 0.2% of
        the round wall (multi-second rounds sample fresh every row),
        and at least every 25 rows regardless (the gauge is a
        watermark, not a per-round delta; the amortized worst case is
        ~0.1 ms/row)."""
        due = (self._live_cache is None
               or self._rows_since_live >= self._LIVE_REFRESH_ROWS
               or (round_s > 0
                   and self._live_cost_s
                   <= self._LIVE_BUDGET_FRAC * round_s))
        self._rows_since_live += 1
        if not due:
            return self._live_cache
        try:
            from fedtorch_tpu.utils.tracing import live_buffer_summary
            t0 = time.perf_counter()
            total = live_buffer_summary()["total_bytes"]
            self._live_cost_s = time.perf_counter() - t0
            self._live_cache = float(total)
            self._rows_since_live = 0
        except Exception:
            pass
        return self._live_cache
