"""``fedtorch-tpu compare A B``: noise-aware diff of two run dirs.

This tool makes "did run B regress run A" a machine decision —
FedScale's point that an FL benchmark is only as good as its cross-run
evaluation harness (Lai et al. 2022). It diffs everything the
telemetry records: round/commit rate and per-phase walls, comm volume,
the accuracy trajectory (round-aligned, with a measured max gap for a
tolerance gate to judge),
MFU/HBM gauges, overlap efficiency, event counts, and the captured
program costs (FLOPs, bytes accessed, peak-HBM watermark).

Noise-awareness lives in the GATE FILE, not in hidden thresholds: the
compare document records raw values, deltas and fractional deltas; a
``--gate gates.json`` names which metrics are binding and how much
drift is tolerated (wall-clock gates in fractions wide enough for a
shared box's noise envelope; byte/count gates exact). Exit code is the
contract: 0 = compared, nothing gated regressed; 1 = >= 1 gated
regression; 2 = unusable input (missing run dir, invalid gate file).

Stdlib-only, never imports jax (the ``tools/report.py`` rule,
asserted in tests); torn-tail and restart-stitching tolerant via the
shared ``telemetry.schema`` loader.

Usage::

    fedtorch-tpu compare A B [--gate gates.json] [--json] [--out F]
    python -m fedtorch_tpu.tools.compare A B
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

COMPARE_SCHEMA = "fedtorch_tpu.run_compare/v1"
GATES_SCHEMA = "fedtorch_tpu.compare_gates/v1"

# the gate-file condition vocabulary (anything else is a hard error —
# a typo'd gate that silently never fires is worse than no gate)
GATE_CHECKS = ("max_increase_frac", "max_decrease_frac",
               "max_increase_abs", "max_decrease_abs",
               "max_b", "min_b")

_EPS = 1e-12


def _entry(a: Optional[float], b: Optional[float]) -> Optional[Dict]:
    """One compared metric: raw sides, absolute and fractional delta
    (fraction relative to |a|; None when a side is missing)."""
    if a is None and b is None:
        return None
    out: Dict = {"a": a, "b": b}
    if a is not None and b is not None:
        out["delta"] = b - a
        out["frac"] = (b - a) / max(abs(a), _EPS)
    return out


def _mean_gauge(rows: List[Dict], key: str) -> Optional[float]:
    vals = [float(r[key]) for r in rows
            if isinstance(r.get(key), (int, float))
            and not isinstance(r.get(key), bool)]
    return sum(vals) / len(vals) if vals else None


def _summary(run_dir: str) -> Tuple[Dict, List[Dict]]:
    from fedtorch_tpu.tools.report import load_run, summarize
    run = load_run(run_dir)  # parsed once; summarize reuses it
    return summarize(run_dir, run=run), run["rows"]


def _trajectory(rows_a: List[Dict], rows_b: List[Dict]) -> Dict:
    """Round-aligned accuracy comparison over the common rounds: the
    max and final gaps a tolerance gate judges — two same-config runs
    differing only in noise track each other; a regressed one drifts."""
    by_a = {r["round"]: r for r in rows_a}
    by_b = {r["round"]: r for r in rows_b}
    common = sorted(set(by_a) & set(by_b))
    out: Dict = {"rounds_compared": len(common)}
    for field in ("acc", "loss", "test_top1"):
        gaps = [float(by_b[r][field]) - float(by_a[r][field])
                for r in common
                if field in by_a[r] and field in by_b[r]]
        if gaps:
            out[f"{field}_max_abs_gap"] = max(abs(g) for g in gaps)
            out[f"{field}_final_delta"] = gaps[-1]
    return out


def compare_runs(dir_a: str, dir_b: str) -> Dict:
    """The compare document (schema ``fedtorch_tpu.run_compare/v1``).
    Raises ``FileNotFoundError`` when either side is not a run dir."""
    sum_a, rows_a = _summary(dir_a)
    sum_b, rows_b = _summary(dir_b)
    metrics: Dict[str, Dict] = {}

    def add(name: str, a, b) -> None:
        e = _entry(
            float(a) if isinstance(a, (int, float))
            and not isinstance(a, bool) else None,
            float(b) if isinstance(b, (int, float))
            and not isinstance(b, bool) else None)
        if e is not None:
            metrics[name] = e

    for key in ("rounds", "round_s_mean_steady", "rounds_per_s_steady",
                "compile_round_s", "comm_bytes_total",
                "comm_bytes_per_round", "final_loss", "final_acc",
                "final_test_top1", "best_test_top1", "torn_lines",
                "restarts"):
        add(key, sum_a.get(key), sum_b.get(key))
    # per-phase mean wall per covered round (the summarize table holds
    # totals + counts; a run with more eval rounds must not read as an
    # eval regression)
    for side_sum, side in ((sum_a, "a"), (sum_b, "b")):
        side_sum["_phase_mean"] = {
            name: total / count
            for name, total, _share, count in side_sum.get("phases")
            or [] if count}
    for name in sorted(set(sum_a["_phase_mean"])
                       | set(sum_b["_phase_mean"])):
        add(f"phase.{name}_mean_s", sum_a["_phase_mean"].get(name),
            sum_b["_phase_mean"].get(name))
    # per-round gauges, mean over the rows that carry them
    for key in ("model_flops_utilization", "hbm_program_peak_bytes",
                "hbm_live_bytes", "round_device_min_s",
                "round_host_frac", "stream_depth",
                "stream_store_resident_mb", "stream_store_mapped_mb",
                "ckpt_queue_depth",
                "async_commit_rate", "async_dropouts",
                "cohort_dispersion", "avail_dropped", "deadline_missed",
                "quorum_degraded",
                "client_shards", "cohort_allreduce_bytes",
                "stream_shard_pack_s", "stream_shard_rows"):
        add(f"gauge.{key}", _mean_gauge(rows_a, key),
            _mean_gauge(rows_b, key))
    ov_a, ov_b = sum_a.get("overlap"), sum_b.get("overlap")
    add("overlap_efficiency_mean",
        (ov_a or {}).get("mean"), (ov_b or {}).get("mean"))
    add("overlap_exposed_frac",
        (ov_a or {}).get("exposed_frac"), (ov_b or {}).get("exposed_frac"))
    cp_a = sum_a.get("critical_path") or {}
    cp_b = sum_b.get("critical_path") or {}
    for key in ("device_floor_s", "unattributed_s", "host_frac"):
        add(f"critical_path.{key}", cp_a.get(key), cp_b.get(key))
    pc_a = sum_a.get("program_costs")
    pc_b = sum_b.get("program_costs")
    for key in ("flops", "bytes_accessed", "peak_hbm_bytes"):
        add(f"pc.{key}", (pc_a or {}).get(key), (pc_b or {}).get(key))
    events: Dict[str, Dict] = {}
    ev_a, ev_b = sum_a.get("events") or {}, sum_b.get("events") or {}
    for name in sorted(set(ev_a) | set(ev_b)):
        events[name] = {"a": ev_a.get(name, 0), "b": ev_b.get(name, 0),
                        "delta": ev_b.get(name, 0) - ev_a.get(name, 0)}
    return {
        "schema": COMPARE_SCHEMA,
        "a": {"run_dir": dir_a, "meta": sum_a.get("meta") or {},
              "health_intent": (sum_a.get("health") or {}).get("intent")},
        "b": {"run_dir": dir_b, "meta": sum_b.get("meta") or {},
              "health_intent": (sum_b.get("health") or {}).get("intent")},
        "metrics": metrics,
        "events": events,
        "trajectory": _trajectory(rows_a, rows_b),
    }


# -- gate files ----------------------------------------------------------

def load_gates(path: str) -> Dict:
    """Parse + validate a gate file; raises ``ValueError`` on an
    unknown check name or a non-numeric limit — a typo'd gate must
    fail loudly, not silently never fire."""
    with open(path) as f:
        doc = json.load(f)
    if doc.get("schema") != GATES_SCHEMA:
        raise ValueError(
            f"gate-file schema {doc.get('schema')!r} != {GATES_SCHEMA!r}")
    gates = doc.get("gates")
    if not isinstance(gates, dict) or not gates:
        raise ValueError("gate file carries no 'gates' object")
    for metric, spec in gates.items():
        if not isinstance(spec, dict):
            raise ValueError(f"gate {metric!r} must be an object")
        checks = [k for k in spec if k != "required"]
        if not checks:
            raise ValueError(f"gate {metric!r} names no condition")
        for k in checks:
            if k not in GATE_CHECKS:
                raise ValueError(
                    f"gate {metric!r} uses unknown check {k!r} "
                    f"(known: {GATE_CHECKS})")
            if isinstance(spec[k], bool) \
                    or not isinstance(spec[k], (int, float)):
                raise ValueError(
                    f"gate {metric!r} check {k!r} limit must be a "
                    f"number, got {spec[k]!r}")
    return doc


def _resolve_metric(cmp_doc: Dict, name: str) -> Optional[Dict]:
    if name.startswith("events."):
        rec = cmp_doc["events"].get(name[len("events."):])
        if rec is None:
            return None
        e = dict(rec)
        e["frac"] = (e["delta"] / max(abs(e["a"]), _EPS)
                     if e["a"] is not None else None)
        return e
    if name.startswith("trajectory."):
        v = cmp_doc["trajectory"].get(name[len("trajectory."):])
        return None if v is None else {"a": None, "b": v, "delta": v,
                                       "frac": None}
    return cmp_doc["metrics"].get(name)


def evaluate_gates(cmp_doc: Dict, gates_doc: Dict
                   ) -> Tuple[List[Dict], List[str], List[str]]:
    """``(failures, checked, skipped)``: every gate either fails with
    a named reason, passes (checked), or is skipped because the metric
    is absent on one side (unless ``"required": true`` — then absence
    IS the failure: a regression that deletes the gauge must not pass
    the gate that watches it)."""
    failures: List[Dict] = []
    checked: List[str] = []
    skipped: List[str] = []
    for metric, spec in gates_doc["gates"].items():
        entry = _resolve_metric(cmp_doc, metric)
        required = bool(spec.get("required", False))
        have_pair = entry is not None and entry.get("b") is not None \
            and (entry.get("a") is not None
                 or not any(k.startswith(("max_increase",
                                          "max_decrease"))
                            for k in spec))
        if not have_pair:
            if required:
                failures.append({
                    "metric": metric, "check": "required",
                    "message": f"{metric}: required metric missing "
                               "from one or both runs"})
            else:
                skipped.append(metric)
            continue
        checked.append(metric)
        a, b = entry.get("a"), entry["b"]
        delta, frac = entry.get("delta"), entry.get("frac")
        for check, limit in spec.items():
            if check == "required":
                continue
            bad = None
            if check == "max_increase_frac" and frac is not None \
                    and frac > limit:
                bad = f"+{frac * 100:.2f}% > +{limit * 100:.2f}%"
            elif check == "max_decrease_frac" and frac is not None \
                    and -frac > limit:
                bad = f"{frac * 100:.2f}% < -{limit * 100:.2f}%"
            elif check == "max_increase_abs" and delta is not None \
                    and delta > limit:
                bad = f"delta {delta:g} > {limit:g}"
            elif check == "max_decrease_abs" and delta is not None \
                    and -delta > limit:
                bad = f"delta {delta:g} < -{limit:g}"
            elif check == "max_b" and b > limit:
                bad = f"b={b:g} > {limit:g}"
            elif check == "min_b" and b < limit:
                bad = f"b={b:g} < {limit:g}"
            if bad is not None:
                failures.append({
                    "metric": metric, "check": check, "limit": limit,
                    "a": a, "b": b, "delta": delta, "frac": frac,
                    "message": f"{metric}: {bad}"})
    return failures, checked, skipped


# -- rendering -----------------------------------------------------------

def _fmt(v) -> str:
    if v is None:
        return "-"
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def render(cmp_doc: Dict, failures: Optional[List[Dict]] = None) -> str:
    failed = {f["metric"] for f in failures or []}
    lines = [f"compare: A={cmp_doc['a']['run_dir']} "
             f"(intent={cmp_doc['a']['health_intent']})  vs  "
             f"B={cmp_doc['b']['run_dir']} "
             f"(intent={cmp_doc['b']['health_intent']})"]
    lines.append(f"{'metric':<32} {'A':>14} {'B':>14} "
                 f"{'delta':>12} {'frac':>9}")
    for name, e in cmp_doc["metrics"].items():
        frac = e.get("frac")
        mark = "  FAIL" if name in failed else ""
        lines.append(
            f"{name:<32} {_fmt(e.get('a')):>14} {_fmt(e.get('b')):>14} "
            f"{_fmt(e.get('delta')):>12} "
            f"{(f'{frac * 100:+.2f}%' if frac is not None else '-'):>9}"
            f"{mark}")
    tr = cmp_doc["trajectory"]
    lines.append(
        f"trajectory: {tr.get('rounds_compared', 0)} common rounds"
        + "".join(f"  {k}={v:.4g}" for k, v in sorted(tr.items())
                  if k != "rounds_compared"))
    diff_ev = {n: e for n, e in cmp_doc["events"].items()
               if e["delta"] or f"events.{n}" in failed}
    if diff_ev:
        lines.append("event deltas: " + "  ".join(
            f"{n} {e['a']}->{e['b']}"
            + (" FAIL" if f"events.{n}" in failed else "")
            for n, e in sorted(diff_ev.items())))
    for f in failures or []:
        lines.append(f"GATE FAIL [{f.get('check')}] {f['message']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="fedtorch-tpu compare",
        description="Noise-aware diff of two run dirs, optionally "
                    "gated (docs/observability.md 'Operating and "
                    "comparing runs'). Exit 0 = no gated regression, "
                    "1 = gated regression, 2 = unusable input.")
    p.add_argument("run_a", help="baseline run dir (A)")
    p.add_argument("run_b", help="candidate run dir (B)")
    p.add_argument("--gate", default=None, metavar="GATES_JSON",
                   help="gate file (schema "
                        "fedtorch_tpu.compare_gates/v1); without it "
                        "the diff is informational and always exits 0")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the compare document (plus gate "
                        "results) as JSON instead of the table")
    p.add_argument("--out", default=None, metavar="FILE",
                   help="also write the JSON document to FILE")
    args = p.parse_args(argv)
    try:
        cmp_doc = compare_runs(args.run_a, args.run_b)
    except (OSError, ValueError) as e:
        # FileNotFoundError (not a run dir), PermissionError (a
        # mis-permissioned artifact mount), a corrupt document — all
        # "unusable input" (exit 2), never a fake gated regression
        print(f"compare: {e}", file=sys.stderr)
        return 2
    failures: List[Dict] = []
    if args.gate is not None:
        try:
            gates = load_gates(args.gate)
        except (OSError, ValueError, json.JSONDecodeError) as e:
            print(f"compare: gate file {args.gate}: {e}",
                  file=sys.stderr)
            return 2
        failures, checked, skipped = evaluate_gates(cmp_doc, gates)
        cmp_doc["gate"] = {
            "path": args.gate, "failures": failures,
            "checked": checked, "skipped": skipped,
            "pass": not failures}
    if args.out:
        try:
            with open(args.out, "w") as f:
                json.dump(cmp_doc, f, indent=2, sort_keys=True)
        except OSError as e:
            print(f"compare: --out {args.out}: {e}", file=sys.stderr)
            return 2
    if args.as_json:
        print(json.dumps(cmp_doc, indent=2, sort_keys=True,
                         default=str))
    else:
        print(render(cmp_doc, failures))
    if failures:
        print(f"compare: {len(failures)} gated regression(s)",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
