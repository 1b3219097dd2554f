"""Profiler-trace attribution: bucket a captured XLA trace's device
time into named op categories (device-side observability, pillar 2 of
docs/observability.md "Device-side").

``jax.profiler.start_trace(dir)`` writes a Chrome-trace
``plugins/profile/<ts>/<host>.trace.json.gz`` under its capture dir.
This tool turns any capture dir into an attribution table: every
device op event — the events carrying XLA's ``hlo_op``/``hlo_module``
args (the CPU backend's Eigen/TfrtCpuClient lanes emit them too, which
is what makes this testable in tier-1), or
living on a ``/device:*`` "XLA Ops" lane (TPU/GPU) — is bucketed by
HLO op name into the taxonomy below, nested events are self-time
split, and the per-lane gap becomes the ``idle_gap`` category.

Taxonomy (ordered; first match wins — so ``reduce-scatter`` is
collective, ``reduce_add_fusion`` is reduce, a bare ``fusion.N`` loop
fusion is elementwise):

* ``collective``         — all-reduce/all-gather/reduce-scatter/
                           all-to-all/collective-permute (ICI/DCN time)
* ``infeed_outfeed_h2d`` — infeed/outfeed/copy-start/copy-done/
                           send/recv (host<->device transfers)
* ``matmul_conv_mxu``    — convolution/dot/matmul/einsum (MXU work —
                           the only bucket the roofline counts)
* ``reduce``             — reduce(-window)/arg-min-max/sort/cumsum/
                           select-and-scatter
* ``copy_reshape_transpose`` — copy/reshape/transpose/bitcast/slice/
                           gather/scatter/pad/concatenate/broadcast
* ``elementwise``        — pointwise math, converts, RNG, loop fusions
* ``control_flow``       — while/conditional/call shells (self time:
                           loop bookkeeping a scanned round pays every
                           local step)
* ``idle_gap``           — device-lane wall not covered by any op
* ``other``              — anything unmatched (the invariant keeps
                           this < 5%)

**Invariant**: ``attributed_frac`` (everything except ``other``) must
cover >= 95% of device time. ``fedtorch-tpu report --device <dir>``
renders the same table; standalone:

    python -m fedtorch_tpu.tools.trace_attrib <capture_dir> \\
        [--out attrib.json] [--render attrib.txt]

Stdlib-only (gzip + json): runs on a monitor box against a mounted
capture dir, never initializes JAX.
"""
from __future__ import annotations

import argparse
import glob
import gzip
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

TRACE_ATTRIB_SCHEMA = "fedtorch_tpu.trace_attrib/v1"

ATTRIBUTED_MIN_FRAC = 0.95

# ordered (category, name-pattern) rules; matched case-insensitively
# against the HLO op/event name, first hit wins
CATEGORY_RULES: List[Tuple[str, "re.Pattern"]] = [
    ("collective", re.compile(
        r"all-reduce|all-gather|reduce-scatter|all-to-all|"
        r"collective|cross-replica", re.I)),
    ("infeed_outfeed_h2d", re.compile(
        r"infeed|outfeed|copy-start|copy-done|\bsend\b|\brecv\b|"
        r"transfer", re.I)),
    ("matmul_conv_mxu", re.compile(
        r"conv(?!ert)|\bdot\b|dot[._\-]|gemm|matmul|einsum", re.I)),
    ("reduce", re.compile(
        r"reduce|arg-?max|arg-?min|\bsort\b|sort[._\-]|cumsum|"
        r"cumulative|select-and-scatter|top-?k", re.I)),
    ("copy_reshape_transpose", re.compile(
        r"copy|reshape|transpose|bitcast|slice|gather|scatter|\bpad\b|"
        r"pad[._\-]|concat|reverse|broadcast|tuple", re.I)),
    ("elementwise", re.compile(
        r"fusion|add|sub|mul|div|max|min|tanh|exp\b|exp[._\-]|"
        r"exponential|expm1|log|pow|sqrt|rsqrt|sigmoid|logistic|"
        r"select|compare|convert|clamp|\band\b|\bor\b|\bxor\b|"
        r"\bnot\b|neg|abs|sign|shift|floor|ceil|round|rem\b|"
        r"remainder|sin|cos|atan|erf|rng|threefry|iota|constant|"
        r"is-finite|relu|softmax|map\b|map[._\-]", re.I)),
    # the while/conditional shells around lax.scan bodies: their SELF
    # time (loop-condition eval, iteration buffer shuffling) is real
    # device time a scan-shaped round program pays every local step —
    # a named line item, not "other". custom-call stays unknown.
    ("control_flow", re.compile(
        r"\bwhile\b|conditional|(?<!custom-)\bcall\b|\bcase\b", re.I)),
]

CATEGORIES = tuple(c for c, _ in CATEGORY_RULES) + ("idle_gap", "other")


def categorize(name: str) -> str:
    for cat, pat in CATEGORY_RULES:
        if pat.search(name):
            return cat
    return "other"


# -- trace discovery and parsing ----------------------------------------


def find_trace_files(path: str) -> List[str]:
    """Every trace file under ``path``: the jax profiler's
    ``plugins/profile/<ts>/*.trace.json.gz`` layout at any depth, plus
    plain ``*.trace.json`` twins (checked-in fixtures), plus ``path``
    itself when it already names a trace file."""
    if os.path.isfile(path):
        return [path]
    found: List[str] = []
    for pat in ("**/*.trace.json.gz", "**/*.trace.json",
                "**/trace.json.gz"):
        found.extend(glob.glob(os.path.join(glob.escape(path), pat),
                               recursive=True))
    return sorted(set(found))


def load_trace_events(path: str) -> List[Dict]:
    """The ``traceEvents`` list of one (possibly gzipped) Chrome trace.
    Raises ``ValueError`` with the offending path on malformed input —
    a truncated capture must say so, not attribute garbage."""
    opener = gzip.open if path.endswith(".gz") else open
    try:
        with opener(path, "rb") as f:
            doc = json.loads(f.read().decode("utf-8", errors="replace"))
    except (OSError, json.JSONDecodeError, EOFError) as e:
        raise ValueError(f"{path}: not a readable Chrome trace "
                         f"({type(e).__name__}: {e})") from e
    evs = doc.get("traceEvents") if isinstance(doc, dict) else None
    if not isinstance(evs, list):
        raise ValueError(f"{path}: no traceEvents list — not a Chrome "
                         "trace export")
    return evs


def _select_device_events(events: List[Dict]) -> List[Dict]:
    """The device op events: complete (``ph='X'``) events that carry
    XLA's ``hlo_op``/``hlo_module`` args (every backend), or sit on an
    'XLA Ops' lane of a ``/device:*`` process (TPU/GPU traces, where
    per-op args can be elided)."""
    procs: Dict = {}
    threads: Dict = {}
    for e in events:
        if e.get("ph") == "M":
            if e.get("name") == "process_name":
                procs[e.get("pid")] = str(
                    (e.get("args") or {}).get("name", ""))
            elif e.get("name") == "thread_name":
                threads[(e.get("pid"), e.get("tid"))] = str(
                    (e.get("args") or {}).get("name", ""))
    out = []
    for e in events:
        if e.get("ph") != "X" or "ts" not in e:
            continue
        args = e.get("args") or {}
        if "hlo_op" in args or "hlo_module" in args:
            out.append(e)
            continue
        proc = procs.get(e.get("pid"), "")
        lane = threads.get((e.get("pid"), e.get("tid")), "")
        if "/device:" in proc and "XLA Ops" in lane:
            out.append(e)
    return out


def _merge_intervals(intervals: List[Tuple[float, float]]
                     ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]

# the idle window keeps >= this share of device busy time: the
# profiler occasionally flushes a stray event from a pre-window
# execution into the buffer, and a microsecond op seconds away from
# the real cluster must not read as seconds of device idle
_IDLE_TRIM_FRAC = 0.005


def _busy_span_idle(intervals: List[Tuple[float, float]]
                    ) -> Tuple[float, float, float]:
    """(busy, span, idle) microseconds. ``busy`` is the union of all
    op intervals; ``span``/``idle`` are measured over the trimmed
    window holding >= 99% of the busy mass (up to 0.5% dropped per
    side), so stray out-of-window events don't inflate the gap."""
    merged = _merge_intervals(intervals)
    if not merged:
        return 0.0, 0.0, 0.0
    busy = sum(e - s for s, e in merged)
    lo, hi = 0, len(merged) - 1
    lead = trail = 0.0
    while lo < hi and lead + (merged[lo][1] - merged[lo][0]) \
            <= _IDLE_TRIM_FRAC * busy:
        lead += merged[lo][1] - merged[lo][0]
        lo += 1
    while hi > lo and trail + (merged[hi][1] - merged[hi][0]) \
            <= _IDLE_TRIM_FRAC * busy:
        trail += merged[hi][1] - merged[hi][0]
        hi -= 1
    span = merged[hi][1] - merged[lo][0]
    in_window = busy - lead - trail
    return busy, span, max(span - in_window, 0.0)


def _lane_self_times(lane_events: List[Dict]
                     ) -> List[Tuple[str, float]]:
    """(name, self-duration) per event on one lane: a nested child's
    duration is subtracted from its enclosing parent, so module- or
    region-level wrappers don't double-count the ops they contain."""
    evs = sorted(lane_events,
                 key=lambda e: (e["ts"], -(e.get("dur") or 0.0)))
    rows: List[List] = []   # [name, dur, child_dur]
    stack: List[int] = []   # indices into rows, innermost last
    ends: List[float] = []
    for e in evs:
        ts = float(e["ts"])
        dur = float(e.get("dur") or 0.0)
        while stack and ts >= ends[stack[-1]] - 1e-9:
            stack.pop()
        if stack:
            rows[stack[-1]][2] += dur
        rows.append([str(e.get("name", "?")), dur, 0.0])
        ends.append(ts + dur)
        stack.append(len(rows) - 1)
    return [(name, max(dur - child, 0.0)) for name, dur, child in rows]


# -- attribution --------------------------------------------------------


def attribute_events(events: List[Dict]) -> Dict:
    """Attribute a flat device-event list (one trace file's worth)."""
    by_lane: Dict[Tuple, List[Dict]] = {}
    for e in events:
        by_lane.setdefault((e.get("pid"), e.get("tid")), []).append(e)

    cat_us: Dict[str, float] = {}
    cat_events: Dict[str, int] = {}
    op_us: Dict[str, float] = {}
    op_cat: Dict[str, str] = {}
    op_events: Dict[str, int] = {}
    intervals: List[Tuple[float, float]] = []
    for lane_events in by_lane.values():
        for e in lane_events:
            ts = float(e["ts"])
            intervals.append((ts, ts + float(e.get("dur") or 0.0)))
        for name, self_us in _lane_self_times(lane_events):
            cat = categorize(name)
            cat_us[cat] = cat_us.get(cat, 0.0) + self_us
            cat_events[cat] = cat_events.get(cat, 0) + 1
            # op key without the SSA suffix, so conv.1/conv.2 pool
            op = re.sub(r"[.\d]+$", "", name) or name
            op_us[op] = op_us.get(op, 0.0) + self_us
            op_events[op] = op_events.get(op, 0) + 1
            op_cat.setdefault(op, cat)

    busy, span, idle = _busy_span_idle(intervals)
    return {"cat_us": cat_us, "cat_events": cat_events, "op_us": op_us,
            "op_cat": op_cat, "op_events": op_events, "span_us": span,
            "busy_us": busy, "idle_us": idle,
            "lanes": len(by_lane), "events": len(events)}


def attribute(path: str) -> Dict:
    """The full attribution document for a capture dir (or a single
    trace file): every trace file's device events bucketed, summed,
    and checked against the >= 95%-attributed invariant."""
    files = find_trace_files(path)
    parts = []
    for f in files:
        evs = _select_device_events(load_trace_events(f))
        if evs:
            parts.append(attribute_events(evs))

    doc: Dict = {
        "schema": TRACE_ATTRIB_SCHEMA,
        "source": path,
        "trace_files": files,
        "device_lanes": sum(p["lanes"] for p in parts),
        "device_events": sum(p["events"] for p in parts),
    }
    if not parts:
        doc.update(total_us=0.0, categories={}, top_ops=[],
                   attributed_frac=None, attributed_ok=False,
                   note=("no device op events found (no trace files, "
                         "or none carrying hlo_op/XLA Ops lanes) — "
                         "nothing to attribute"))
        return doc

    cat_us: Dict[str, float] = {}
    cat_events: Dict[str, int] = {}
    op_us: Dict[str, float] = {}
    op_cat: Dict[str, str] = {}
    op_events: Dict[str, int] = {}
    idle = busy = span = 0.0
    for p in parts:
        for c, v in p["cat_us"].items():
            cat_us[c] = cat_us.get(c, 0.0) + v
        for c, v in p["cat_events"].items():
            cat_events[c] = cat_events.get(c, 0) + v
        for o, v in p["op_us"].items():
            op_us[o] = op_us.get(o, 0.0) + v
            op_events[o] = op_events.get(o, 0) + p["op_events"][o]
            op_cat.setdefault(o, p["op_cat"][o])
        idle += p["idle_us"]
        busy += p["busy_us"]
        span += p["span_us"]
    cat_us["idle_gap"] = idle
    cat_events.setdefault("idle_gap", 0)

    total = sum(cat_us.values())
    categories = {
        c: {"time_us": round(cat_us.get(c, 0.0), 3),
            "frac": round(cat_us.get(c, 0.0) / total, 6) if total else 0.0,
            "events": cat_events.get(c, 0)}
        for c in CATEGORIES if c in cat_us or c == "idle_gap"}
    attributed = 1.0 - (cat_us.get("other", 0.0) / total) if total \
        else None
    top = sorted(op_us.items(), key=lambda kv: -kv[1])[:15]
    doc.update(
        span_us=round(span, 3), busy_us=round(busy, 3),
        total_us=round(total, 3),
        categories=categories,
        attributed_frac=round(attributed, 6)
        if attributed is not None else None,
        attributed_ok=bool(attributed is not None
                           and attributed >= ATTRIBUTED_MIN_FRAC),
        top_ops=[{"name": o, "category": op_cat[o],
                  "time_us": round(us, 3), "events": op_events[o]}
                 for o, us in top],
    )
    return doc


# -- rendering ----------------------------------------------------------


def _fmt_us(us: float) -> str:
    if us >= 1e6:
        return f"{us / 1e6:.3f} s"
    if us >= 1e3:
        return f"{us / 1e3:.2f} ms"
    return f"{us:.1f} us"


def render(doc: Dict) -> str:
    lines = [f"device-time attribution: {doc['source']}"]
    if not doc.get("categories"):
        lines.append(f"  {doc.get('note', 'nothing to attribute')}")
        return "\n".join(lines)
    lines.append(
        f"  {doc['device_events']} device op events on "
        f"{doc['device_lanes']} lane(s); span {_fmt_us(doc['span_us'])}"
        f", busy {_fmt_us(doc['busy_us'])}")
    lines.append("  category                  time          share  events")
    for cat in CATEGORIES:
        rec = doc["categories"].get(cat)
        if rec is None:
            continue
        lines.append(f"  {cat:<24} {_fmt_us(rec['time_us']):>12}  "
                     f"{rec['frac'] * 100:5.1f}%  {rec['events']:6d}")
    frac = doc["attributed_frac"]
    if frac is None:
        # events selected but every duration zero/absent: nothing to
        # apportion — say so instead of dividing by the zero total
        lines.append("  attributed: n/a (device events carry no "
                     "durations)")
    else:
        flag = "OK" if doc["attributed_ok"] else \
            f"BELOW the {ATTRIBUTED_MIN_FRAC * 100:.0f}% invariant"
        lines.append(f"  attributed: {frac * 100:.1f}% of device time "
                     f"into named categories ({flag})")
    if doc.get("top_ops"):
        lines.append("  top ops by self time:")
        for op in doc["top_ops"][:8]:
            lines.append(
                f"    {op['name'][:36]:<36} {_fmt_us(op['time_us']):>12}"
                f"  [{op['category']}] x{op['events']}")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="python -m fedtorch_tpu.tools.trace_attrib",
        description="Attribute a jax.profiler capture dir's device "
                    "time into op categories "
                    "(docs/observability.md 'Device-side')")
    p.add_argument("capture_dir",
                   help="dir holding plugins/profile/*/... (or a "
                        "trace.json[.gz] file directly)")
    p.add_argument("--out", default=None,
                   help="also write the attribution JSON here")
    p.add_argument("--render", default=None,
                   help="also write the rendered table here")
    args = p.parse_args(argv)
    try:
        doc = attribute(args.capture_dir)
    except ValueError as e:
        print(f"trace_attrib: {e}", file=sys.stderr)
        return 2
    text = render(doc)
    print(text)
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
    if args.render:
        os.makedirs(os.path.dirname(args.render) or ".", exist_ok=True)
        with open(args.render, "w") as f:
            f.write(text + "\n")
    if not doc.get("categories"):
        return 2
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
