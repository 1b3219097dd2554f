"""From the run's profile and host spans to the numbers by stage: what
the round program's ``jax.named_scope`` names (``fed.*``, ``eval.*``),
the launcher's ``TraceAnnotation`` spans and JAX's compile spans say.

The device half reads the profiler's ``.xplane.pb`` once a run (it is
still on disk at ``benchmark/.cache/<cell>/trace`` when the per-layer
readers run) and keeps the result in ``ctx["stages"]``: device self
seconds by stage inside the round module's executions and inside the
evaluation program's, the host annotations on the trace's own clock,
the idle gaps named by the annotation over them (no shift between two
clocks), and the device programs that start under an annotation. The
span half reads the
launcher's ``trace.json`` spans that ``ctx["spans"]`` already holds.

Against a program that has no scopes, annotations or compile spans
(the parent of the PR that added them) every reader that needs them
returns None.

The reduction works on a neutral event form, so that it can be checked
on a small recorded list (``benchmark/tests/test_stage_reduce.py``):
an operation is ``{"lane", "name", "start", "dur", "stage", "conv"}``
(seconds; ``stage`` the first scope name or None; ``inner`` optionally
the innermost one; ``conv`` None where the trace gives no category), a
module execution
``{"lane", "name", "start", "dur"}``, an annotation ``(name, start,
end, args)``.
"""
from __future__ import annotations

import json
import os
import re
import statistics
import warnings
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace_reduce, window

# the first fed.* / eval.* component of an operation's framework name
# (``jit(round_fn)/jit(main)/vmap(fed.local_steps)/while/body/...``:
# transformations wrap the components, so the match is not anchored)
STAGE = re.compile(r"\b((?:fed|eval)\.[a-z_]+)")
GATHER_STAGES = ("fed.select", "fed.gather", "fed.pre_round")
LOCAL_STAGES = ("fed.local_steps", "fed.augment", "fed.forward_backward",
                "fed.opt_step")
COMMIT_STAGES = ("fed.wire", "fed.guard", "fed.aggregate",
                 "fed.server_step", "fed.scatter", "fed.metrics")
# the launcher's spans that name a gap, innermost first where they nest
GAP_SPANS = ("round.dispatch", "round.wait", "scalar_fetch",
             "round.record", "cost_capture", "eval", "checkpoint.snapshot",
             "checkpoint.write", "checkpoint", "round")
COMPILE_SPANS = ("jax.trace", "jax.lower", "jax.compile", "jax.cache_load")


def stage_of(framework_name: Optional[str]) -> Optional[str]:
    m = STAGE.search(framework_name or "")
    return m.group(1) if m else None


def inner_stage_of(framework_name: Optional[str]) -> Optional[str]:
    """The last such component: ``fed.forward_backward`` inside
    ``fed.local_steps``."""
    found = STAGE.findall(framework_name or "")
    return found[-1] if found else None


# -- the device half, on the neutral form ---------------------------------

def _module_name(event_name: str) -> str:
    """``jit_round_fn(18264200309776686854)`` without the program id."""
    return re.sub(r"\(\d+\)$", "", event_name)


def round_module(modules: Sequence[dict], rounds: int
                 ) -> Tuple[Optional[str], Dict[str, List[Tuple]]]:
    """The round program as the runner picks it (the module run once a
    traced round with the longest executions) and its executions per
    lane as (start, end)."""
    by_name: Dict[str, Dict[str, List[Tuple[float, float]]]] = {}
    for m in modules:
        name = _module_name(m["name"])
        by_name.setdefault(name, {}).setdefault(m["lane"], []).append(
            (m["start"], m["start"] + m["dur"]))
    best, best_s = None, -1.0
    for name, lanes in by_name.items():
        runs = sum(len(w) for w in lanes.values()) / len(lanes)
        total = sum(t - s for w in lanes.values() for s, t in w)
        if abs(runs - rounds) < 0.5 and total > best_s:
            best, best_s = name, total
    return best, by_name.get(best, {})


def stage_seconds(ops: Sequence[dict],
                  windows: Dict[str, List[Tuple[float, float]]]) -> Dict:
    """Self seconds of the operations that run inside ``windows`` (per
    lane), summed by stage and averaged over the lanes: ``stage_s``
    (``local_s``: what lies under ``fed.local_steps``, by the innermost
    scope), ``unstaged_s`` with its operations by name, ``conv_s``
    (operations that hold a convolution or a dot; None where no
    operation has a category) and ``self_s``, the sum of all."""
    lanes = sorted(windows)
    stage_s: Dict[str, float] = {}
    local_s: Dict[str, float] = {}
    unstaged_ops: Dict[str, float] = {}
    conv_s = total = 0.0
    categorised = False
    for lane in lanes:
        wins = sorted(windows[lane])
        evs = sorted(
            (e for e in ops if e["lane"] == lane and any(
                w0 - 1e-9 <= e["start"] and e["start"] + e["dur"]
                <= w1 + 1e-9 for w0, w1 in wins)),
            key=lambda e: (e["start"], -e["dur"]))
        # lane_self_times keeps this order: a while shell gives up the
        # time of its body, a fusion that of nothing
        for e, (_, self_s) in zip(evs, trace_reduce.lane_self_times(evs)):
            total += self_s
            categorised = categorised or e.get("conv") is not None
            if e.get("conv"):
                conv_s += self_s
            if e.get("stage"):
                stage_s[e["stage"]] = stage_s.get(e["stage"], 0.0) + self_s
                if e["stage"] == "fed.local_steps":
                    inner = e.get("inner") or e["stage"]
                    local_s[inner] = local_s.get(inner, 0.0) + self_s
            else:
                op = re.sub(r"[.\d]+$", "", e["name"]) or e["name"]
                unstaged_ops[op] = unstaged_ops.get(op, 0.0) + self_s
    n = max(len(lanes), 1)
    return {
        "stage_s": {k: v / n for k, v in stage_s.items()},
        "local_s": {k: v / n for k, v in local_s.items()},
        "unstaged_s": sum(unstaged_ops.values()) / n,
        "unstaged_ops": {k: v / n for k, v in unstaged_ops.items()},
        "conv_s": conv_s / n if categorised else None,
        "self_s": total / n,
    }


def eval_seconds(ops: Sequence[dict], modules: Sequence[dict]
                 ) -> Tuple[float, float]:
    """(self seconds under ``eval.*`` scopes, executions a lane) of the
    evaluation program: the module executions that hold an operation
    with such a scope."""
    names = set()
    for e in ops:
        if (e.get("stage") or "").startswith("eval."):
            names.update(
                _module_name(m["name"]) for m in modules
                if m["lane"] == e["lane"]
                and m["start"] - 1e-9 <= e["start"] < m["start"] + m["dur"])
    windows: Dict[str, List[Tuple[float, float]]] = {}
    for m in modules:
        if _module_name(m["name"]) in names:
            windows.setdefault(m["lane"], []).append(
                (m["start"], m["start"] + m["dur"]))
    if not windows:
        return 0.0, 0.0
    stage_s = stage_seconds(ops, windows)["stage_s"]
    return (sum(v for k, v in stage_s.items() if k.startswith("eval.")),
            sum(len(w) for w in windows.values()) / len(windows))


def idle_gaps(ops: Sequence[dict], span: Tuple[float, float]
              ) -> List[Tuple[float, float]]:
    """Intervals of ``span`` in which no operation runs on the first
    lane."""
    lanes = sorted({e["lane"] for e in ops})
    if not lanes:
        return []
    merged = trace_reduce.merge_intervals(
        [(e["start"], e["start"] + e["dur"]) for e in ops
         if e["lane"] == lanes[0]])
    gaps, edge = [], span[0]
    for s, t in merged:
        if s > edge:
            gaps.append((edge, min(s, span[1])))
        edge = max(edge, t)
        if edge >= span[1]:
            break
    if span[1] > edge:
        gaps.append((edge, span[1]))
    return [g for g in gaps if g[1] > g[0]]


def label_gaps(gaps: Sequence[Tuple[float, float]],
               annotations: Sequence[Tuple], top: int = 12) -> List[List]:
    """Each idle gap split among the annotations that lie over it, each
    part named by the innermost (shortest) annotation there; ``none``
    where there is none. Summed by name, longest first."""
    spans = sorted(((a[2] - a[1], a[0], a[1], a[2]) for a in annotations
                    if a[0] in GAP_SPANS))
    total: Dict[str, float] = {}
    for g0, g1 in gaps:
        cuts = sorted({g0, g1} | {t for _, _, s, e in spans
                                  for t in (s, e) if g0 < t < g1})
        for c0, c1 in zip(cuts, cuts[1:]):
            mid = (c0 + c1) / 2
            name = next((n for _, n, s, e in spans if s <= mid < e),
                        "none")
            total[name] = total.get(name, 0.0) + (c1 - c0)
    return [[k, v] for k, v in
            sorted(total.items(), key=lambda kv: -kv[1])[:top]]


def programs_under(modules: Sequence[dict], annotations: Sequence[Tuple],
                   names: Sequence[str], skip: Optional[str] = None
                   ) -> List[Tuple[str, float]]:
    """(module name, seconds) of every device program execution of the
    first lane, other than ``skip``, that starts inside an annotation
    whose name is in ``names``."""
    lanes = sorted({m["lane"] for m in modules})
    wins = sorted((a[1], a[2]) for a in annotations if a[0] in names)
    out = []
    for m in modules:
        name = _module_name(m["name"])
        if m["lane"] == lanes[0] and name != skip and any(
                w0 <= m["start"] < w1 for w0, w1 in wins):
            out.append((name, m["dur"]))
    return out


def reduce_stages(ops: Sequence[dict], modules: Sequence[dict],
                  annotations: Sequence[Tuple], rounds: int) -> Dict:
    """Everything the device readers need, from the neutral form."""
    name, windows = round_module(modules, rounds)
    out = stage_seconds(ops, windows) if name else {
        "stage_s": {}, "local_s": {}, "unstaged_s": 0.0, "unstaged_ops": {},
        "conv_s": None, "self_s": 0.0}
    out["round_module"] = name
    out["eval_s"], out["eval_runs"] = eval_seconds(ops, modules)
    out["rounds"] = rounds
    out["annotated"] = bool(annotations)
    if ops:
        w0 = min(e["start"] for e in ops)
        w1 = max([e["start"] + e["dur"] for e in ops]
                 + [a[2] for a in annotations if a[0] in GAP_SPANS])
        out["gap_labels"] = label_gaps(idle_gaps(ops, (w0, w1)),
                                       annotations)
    else:
        out["gap_labels"] = []
    # the device's plane ran 1-2 ms ahead of the host's in the profiles
    # looked at (PERF.md section 5), so the fetch's first programs start
    # under the end of ``round.wait``; the loop dispatches nothing but
    # the round program before the fetch, so both spans are searched
    fetch: Dict[str, List[float]] = {}
    for mod, dur in programs_under(modules, annotations,
                                   ("round.wait", "scalar_fetch"), name):
        fetch.setdefault(mod, []).append(dur)
    out["fetch_programs"] = {
        k: {"runs": len(v), "seconds": sum(v)} for k, v in fetch.items()}
    return out


# -- reading the profiler's file -------------------------------------------
#
# What the chip's trace showed (TPU v5e, jax 0.9.0; PERF.md section 3): an
# 'XLA Ops' event's own stats are its device offset and duration, and its
# name is the instruction's HLO text without ``metadata={...}``. The
# framework name (``tf_op``: ``jit(round_fn)/vmap(fed.local_steps)/while/
# body/closed_call/fed.augment/squeeze:``) and the HLO category
# (``convolution fusion``, ``loop fusion``, ``data formatting``...) are
# stats of the event's *metadata* record in the plane's table, which
# ``jax.profiler.ProfileData`` does not hand out. So the table is read
# from the file itself: protobuf's wire format, the few fields of
# ``xplane.proto`` named below, nothing but the standard library. Events
# are joined to it by name (the HLO text is unique in a plane's table).

HOST_PLANE = re.compile(r"^/host:")
CONV_CATEGORY = re.compile(r"convolution|\bdot\b|matmul", re.I)


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, wire type, value) of one message: a varint as an
    int, a length-delimited field as bytes, fixed ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = None, i + 8
        elif wire == 5:
            val, i = None, i + 4
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield num, wire, val


def _map_value(entry: bytes) -> Tuple[Optional[int], bytes]:
    """(key, value message) of one entry of a ``map<int64, Message>``."""
    key, rec = None, b""
    for num, _, val in _fields(entry):
        if num == 1:
            key = val
        elif num == 2:
            rec = val
    return key, rec


def _text(message: bytes, field: int) -> str:
    """The first string field ``field`` of a message, or ''."""
    return next((v for k, w, v in _fields(message)
                 if k == field and w == 2), b"").decode("utf-8", "replace")


def read_op_metadata(path: str, want=("tf_op", "hlo_category")
                     ) -> Dict[str, Dict[str, Dict[str, str]]]:
    """``{plane name: {event name: {stat name: text}}}`` for the stats
    ``want`` of every record of each device plane's event-metadata
    table. Field numbers of ``xplane.proto``: XSpace.planes 1;
    XPlane.name 2, .event_metadata 4, .stat_metadata 5 (map entries:
    key 1, value 2); XEventMetadata.name 2, .stats 5; XStatMetadata
    .name 2; XStat.metadata_id 1, .str_value 5, .ref_value 7 (the id of
    a stat-metadata record whose name is the text)."""
    with open(path, "rb") as f:
        space = f.read()
    out: Dict[str, Dict[str, Dict[str, str]]] = {}
    for num, wire, plane in _fields(space):
        if num != 1 or wire != 2:
            continue
        name, stat_names, metas = "", {}, []
        for pnum, pwire, val in _fields(plane):
            if pnum == 2 and pwire == 2:
                name = val.decode("utf-8", "replace")
            elif pnum == 5 and pwire == 2:       # stat_metadata entry
                key, rec = _map_value(val)
                stat_names[key] = _text(rec, 2)
            elif pnum == 4 and pwire == 2:       # event_metadata entry
                metas.append(_map_value(val)[1])
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        table = out.setdefault(name, {})
        for rec in metas:
            ev_name, found = _text(rec, 2), {}
            for k, w, v in _fields(rec):
                if k == 5 and w == 2:
                    sid = text = None
                    for sk, sw, sv in _fields(v):
                        if sk == 1:
                            sid = sv
                        elif sk == 5 and sw == 2:
                            text = sv.decode("utf-8", "replace")
                        elif sk == 7 and sw == 0:
                            text = stat_names.get(sv)
                    if stat_names.get(sid) in want and text is not None:
                        found[stat_names[sid]] = text
            if ev_name:
                table[ev_name] = found
    return out


def op_stage_and_conv(meta: Dict[str, str]
                      ) -> Tuple[Optional[str], Optional[str],
                                 Optional[bool]]:
    """The stage, the innermost scope, and whether the operation holds
    a convolution or a dot, from an event's metadata record: the stage
    components of its framework name (``tf_op``) and its HLO category.
    A record without the one has no stage, without the other None."""
    framework, category = meta.get("tf_op"), meta.get("hlo_category")
    return (stage_of(framework), inner_stage_of(framework),
            bool(CONV_CATEGORY.search(category)) if category else None)


def load_ops(prof, metadata: Dict[str, Dict[str, Dict[str, str]]]
             ) -> List[dict]:
    out = []
    for plane in prof.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        table = metadata.get(plane.name, {})
        cache: Dict[str, Tuple] = {}
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                name = ev.name
                if name not in cache:
                    cache[name] = (trace_reduce.short_name(name),) \
                        + op_stage_and_conv(table.get(name, {}))
                short, stage, inner, conv = cache[name]
                out.append({"lane": plane.name, "name": short,
                            "start": ev.start_ns / 1e9,
                            "dur": ev.duration_ns / 1e9,
                            "stage": stage, "inner": inner,
                            "conv": conv})
    return out


def event_stats(ev) -> Dict:
    """An event's own stats. jaxlib's iterator type over them raises a
    DeprecationWarning when it is first made (nanobind: no
    ``__module__``), which as an error aborts the interpreter from
    inside the extension; it is silenced here and nowhere else."""
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return dict(ev.stats)


def load_annotations(prof, names: Sequence[str] = GAP_SPANS
                     ) -> List[Tuple]:
    """The launcher's spans as the profile holds them: events of the
    host plane whose name is one of ``names``, on the trace's clock."""
    keep = set(names)
    out = []
    for plane in prof.planes:
        if not HOST_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in keep:
                    out.append((ev.name, ev.start_ns / 1e9,
                                (ev.start_ns + ev.duration_ns) / 1e9,
                                event_stats(ev)))
    return sorted(out, key=lambda a: a[1])


def get(ctx) -> Optional[Dict]:
    """The run's stage reduction, made once and kept in ``ctx``; None
    where the run was not traced or the profile is gone."""
    if "stages" in ctx:
        return ctx["stages"]
    ctx["stages"] = None
    if not ctx.get("trace"):
        return None
    bench = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = trace_reduce.find_xplane(os.path.join(
        bench, ".cache", ctx["cell"]["name"], "trace"))
    if path is None:
        return None
    prof = trace_reduce.read_profile(path)
    metadata = read_op_metadata(path)
    modules = trace_reduce.load_device_events(
        prof, line_name=trace_reduce.MODULES_LINE)
    red = reduce_stages(load_ops(prof, metadata), modules,
                        load_annotations(prof),
                        int(ctx["trace"]["rounds"]))
    n = max(red["rounds"], 1)

    def a_round(seconds: Dict[str, float], top: Optional[int] = None):
        return json.dumps({k: round(v / n, 6) for k, v in sorted(
            seconds.items(), key=lambda kv: -kv[1])[:top]})

    print("benchmark: stage seconds a round: " + a_round(red["stage_s"])
          + "; under fed.local_steps by innermost scope "
          + a_round(red["local_s"])
          + "; unstaged " + a_round(red["unstaged_ops"], 5)
          + f"; evaluation {red['eval_s']:.6f} s in "
            f"{red['eval_runs']:g} executions", flush=True)
    print("benchmark: idle gaps by annotation: " + json.dumps(
        [[k, round(v, 6)] for k, v in red["gap_labels"]])
        + "; programs under the fetch (runs, seconds): " + json.dumps(
        {k: [v["runs"], round(v["seconds"], 9)]
         for k, v in red["fetch_programs"].items()}), flush=True)
    ctx["stages"] = red
    return red


def stage_s_per_round(ctx, stages: Sequence[str]) -> Optional[float]:
    """Device self seconds a traced round under ``stages``; None where
    the program carries no stage name at all."""
    red = get(ctx)
    if not red or not red["stage_s"]:
        return None
    return sum(red["stage_s"].get(s, 0.0) for s in stages) \
        / max(red["rounds"], 1)


def local_s_per_round(ctx, inner: str) -> Optional[float]:
    """Device self seconds a traced round under ``fed.local_steps``
    whose innermost scope is ``inner``; None where the program carries
    no stage name at all."""
    red = get(ctx)
    if not red or not red["stage_s"]:
        return None
    return red["local_s"].get(inner, 0.0) / max(red["rounds"], 1)


# -- the span half: the launcher's trace.json --------------------------------

def spans_named(ctx, names: Sequence[str]) -> List[Tuple]:
    keep = set(names)
    return [s for s in ctx["spans"]["spans"] if s[0] in keep]


def train_only_rounds(ctx) -> List[int]:
    """The window's rounds whose iteration holds a train round and
    nothing else: what ``round_s_p50`` is the median over."""
    w = ctx["window"]
    eval_freq = int(ctx["cell"]["traffic_file"]["launcher"].get(
        "eval_freq", 1))
    return [r for r in range(w["first"], w["last"] + 1)
            if not window.is_boundary(r, eval_freq)]


def median_span_s(ctx, name: str) -> Optional[float]:
    """Median over the window's train-only iterations of the seconds
    under spans called ``name`` (summed where a round has several)."""
    by_round: Dict[int, float] = {}
    for _, _, dur, args in spans_named(ctx, (name,)):
        if "round" in args:
            r = int(args["round"])
            by_round[r] = by_round.get(r, 0.0) + dur
    vals = [by_round[r] for r in train_only_rounds(ctx) if r in by_round]
    return statistics.median(vals) if vals else None


def union_s(spans: Sequence[Tuple], inside: Optional[Tuple] = None
            ) -> float:
    """Seconds covered by ``spans`` (nested ones once), clipped to
    ``inside`` = (start, end)."""
    ivs = []
    for _, start, dur, _ in spans:
        s, t = start, start + dur
        if inside is not None:
            s, t = max(s, inside[0]), min(t, inside[1])
        if t > s:
            ivs.append((s, t))
    return sum(t - s for s, t in trace_reduce.merge_intervals(ivs))


def round_span(ctx, r: int) -> Optional[Tuple[float, float]]:
    for _, start, dur, args in spans_named(ctx, ("round",)):
        if args.get("round") == r:
            return (start, start + dur)
    return None
