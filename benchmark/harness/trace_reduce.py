"""From a profiler trace to device numbers: the benchmark's own copy of
the reduction in ``fedtorch_tpu/tools/trace_attrib.py`` (taxonomy,
interval union, self time of nested events), on a neutral event form so
that it reads the profiler's ``.xplane.pb`` with nothing but JAX and
can be checked on a small recorded list (``benchmark/tests``).

An event is ``{"lane": str, "name": str, "start": seconds, "dur":
seconds}``; a lane is one device's line of operations.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

# ordered (category, pattern): first match wins. Copied from
# tools/trace_attrib.py; ``custom-call`` gets its own bucket because the
# wire kernels are Mosaic custom calls and a cell reads their time.
CATEGORY_RULES: List[Tuple[str, "re.Pattern"]] = [
    ("custom_call", re.compile(r"custom-call|custom_call|tpu_custom",
                               re.I)),
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
    ("control_flow", re.compile(
        r"\bwhile\b|conditional|\bcall\b|\bcase\b", re.I)),
]


def categorize(name: str) -> str:
    for cat, pat in CATEGORY_RULES:
        if pat.search(name):
            return cat
    return "other"


def merge_intervals(intervals: Sequence[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals as a sorted disjoint list."""
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def lane_self_times(events: Sequence[dict]) -> List[Tuple[str, float]]:
    """(name, self seconds) per event of one lane: a nested child's
    duration is taken off the event that encloses it, so a ``while``
    shell does not count the operations of its body twice."""
    evs = sorted(events, key=lambda e: (e["start"], -e["dur"]))
    rows: List[List] = []
    stack: List[int] = []
    ends: List[float] = []
    for e in evs:
        end = e["start"] + e["dur"]
        # a child lies wholly inside its parent; an event that only
        # overlaps one (asynchronous operations do) is its sibling
        while stack and (e["start"] >= ends[stack[-1]] - 1e-12
                         or end > ends[stack[-1]] + 1e-12):
            stack.pop()
        if stack:
            rows[stack[-1]][2] += e["dur"]
        rows.append([e["name"], e["dur"], 0.0])
        ends.append(e["start"] + e["dur"])
        stack.append(len(rows) - 1)
    return [(n, max(d - c, 0.0)) for n, d, c in rows]


def reduce_events(events: Sequence[dict], window: Tuple[float, float]
                  ) -> Dict:
    """Busy seconds (interval union, averaged over the lanes), the time
    of each category and operation (self time, summed over lanes then
    divided by the lanes), and the idle gaps of the first lane, all
    clipped to ``window`` = (start, end) seconds."""
    w0, w1 = window
    lanes: Dict[str, List[dict]] = {}
    for e in events:
        s, t = max(e["start"], w0), min(e["start"] + e["dur"], w1)
        if t <= s:
            continue
        lanes.setdefault(e["lane"], []).append(
            {"name": e["name"], "start": s, "dur": t - s})
    n = max(len(lanes), 1)
    busy = 0.0
    cat_s: Dict[str, float] = {}
    op_s: Dict[str, float] = {}
    gaps: List[Tuple[float, float]] = []
    for i, (_, evs) in enumerate(sorted(lanes.items())):
        merged = merge_intervals(
            [(e["start"], e["start"] + e["dur"]) for e in evs])
        busy += sum(t - s for s, t in merged)
        if i == 0:
            edge = w0
            for s, t in merged:
                if s > edge:
                    gaps.append((edge, s))
                edge = max(edge, t)
            if w1 > edge:
                gaps.append((edge, w1))
        for name, self_s in lane_self_times(evs):
            cat = categorize(name)
            cat_s[cat] = cat_s.get(cat, 0.0) + self_s
            op = re.sub(r"[.\d]+$", "", name) or name
            op_s[op] = op_s.get(op, 0.0) + self_s
    return {
        "lanes": len(lanes),
        "window_s": w1 - w0,
        "busy_s": busy / n,
        "category_s": {c: v / n for c, v in cat_s.items()},
        "op_s": {o: v / n for o, v in op_s.items()},
        "gaps": sorted(gaps, key=lambda g: g[0] - g[1]),
    }


def busy_inside(events: Sequence[dict],
                windows: Sequence[Tuple[float, float]]) -> float:
    """Seconds of one lane's operation-interval union that fall inside
    ``windows`` (disjoint intervals, e.g. one program's executions)."""
    merged = merge_intervals(
        [(e["start"], e["start"] + e["dur"]) for e in events])
    total = 0.0
    for w0, w1 in windows:
        for s, t in merged:
            lo, hi = max(s, w0), min(t, w1)
            if hi > lo:
                total += hi - lo
    return total


def module_busy(ops: Sequence[dict], modules: Sequence[dict]) -> Dict:
    """Per program (an event of a lane's 'XLA Modules' line, its name
    without the run's id): executions per lane, and the seconds an
    operation ran inside them, averaged over the lanes."""
    lanes = sorted({m["lane"] for m in modules})
    out: Dict[str, Dict] = {}
    for lane in lanes:
        lane_ops = [e for e in ops if e["lane"] == lane]
        by_name: Dict[str, List[Tuple[float, float]]] = {}
        for m in modules:
            if m["lane"] == lane:
                name = re.sub(r"\(\d+\)$", "", m["name"])
                by_name.setdefault(name, []).append(
                    (m["start"], m["start"] + m["dur"]))
        for name, wins in by_name.items():
            rec = out.setdefault(name, {"runs": 0, "busy_s": 0.0,
                                        "module_s": 0.0})
            rec["runs"] += len(wins) / len(lanes)
            rec["busy_s"] += busy_inside(lane_ops, wins) / len(lanes)
            rec["module_s"] += sum(t - s for s, t in wins) / len(lanes)
    return out


def label_gaps(gaps: Sequence[Tuple[float, float]],
               host_spans: Sequence[Tuple[str, float, float]],
               top: int = 10) -> List[List]:
    """Each idle gap named by the host span that covers most of it
    (``none`` where no span overlaps), summed by name, longest first."""
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        best, best_s = "none", 0.0
        for name, s0, s1 in host_spans:
            ov = min(g1, s1) - max(g0, s0)
            if ov > best_s:
                best, best_s = name, ov
        total[best] = total.get(best, 0.0) + (g1 - g0)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


# -- reading the profiler's file ----------------------------------------

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def short_name(name: str) -> str:
    """An operation's own name out of the HLO text the TPU's trace
    gives as the event name (``%fusion.12 = bf16[...] fusion(...)``),
    with the opcode appended where the name alone hides it."""
    m = re.match(r"%?([^\s=]+)\s*=\s*.*?\s([a-z][a-z0-9\-]*)\(", name)
    if not m:
        return name.lstrip("%")[:80]
    own, opcode = m.group(1), m.group(2)
    return own if opcode.split("-")[0] in own else f"{own}:{opcode}"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        glob.escape(trace_dir), "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_profile(path: str):
    """The profiler's ``.xplane.pb``, parsed once."""
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)


def load_device_events(prof, plane_pat=DEVICE_PLANE,
                       line_name: str = OPS_LINE) -> List[dict]:
    """Events of one line (the operations, by default) of every TPU
    plane, in seconds on the trace's own clock."""
    out: List[dict] = []
    for plane in prof.planes:
        if not plane_pat.match(plane.name):
            continue
        for line in plane.lines:
            if line.name != line_name:
                continue
            for ev in line.events:
                out.append({"lane": plane.name, "name": short_name(ev.name),
                            "start": ev.start_ns / 1e9,
                            "dur": ev.duration_ns / 1e9})
    return out


def describe(prof, per_line: int = 5) -> List[str]:
    """Planes, lines and a few events of each: what to look at by hand
    before trusting the lane selection above."""
    out = []
    for plane in prof.planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:per_line]:
                out.append(f"    {ev.name[:100]!r} start_ns={ev.start_ns} "
                           f"dur_ns={ev.duration_ns}")
    return out
