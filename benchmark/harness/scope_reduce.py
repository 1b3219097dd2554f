"""Device seconds by the model's own scopes (``lm.*``), beside
``stage_reduce``'s federated stages (``fed.*``, ``eval.*``).

The profile is read as ``stage_reduce`` reads it: an operation's
framework name (``tf_op``) comes from the event-metadata table of the
profiler's file, its self seconds from ``trace_reduce.lane_self_times``
over the operations inside the round module's executions. An operation
belongs to the innermost ``lm.<name>`` component of its framework name
(``.../transpose(jvp(lm.delta_rule))/...`` in the backward pass: the
match is not anchored). Made once a run and kept in ``ctx["scopes"]``;
against a program without such scopes, or a run without a trace, every
reader gets None.
"""
from __future__ import annotations

import json
import os
import re
from typing import Dict, Optional

from . import stage_reduce, trace_reduce

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCOPE = re.compile(r"\b(lm\.[a-z_]+)")


def scope_of(framework_name: Optional[str]) -> Optional[str]:
    found = SCOPE.findall(framework_name or "")
    return found[-1] if found else None


def scope_seconds(ops, windows) -> Dict[str, float]:
    """Self seconds by scope of the neutral operations (``lane``,
    ``name``, ``start``, ``dur``, ``scope``) that run inside
    ``windows`` (per lane), averaged over the lanes."""
    out: Dict[str, float] = {}
    for lane in sorted(windows):
        wins = sorted(windows[lane])
        evs = sorted(
            (e for e in ops if e["lane"] == lane and any(
                w0 - 1e-9 <= e["start"] and e["start"] + e["dur"]
                <= w1 + 1e-9 for w0, w1 in wins)),
            key=lambda e: (e["start"], -e["dur"]))
        for e, (_, self_s) in zip(evs, trace_reduce.lane_self_times(evs)):
            if e["scope"]:
                out[e["scope"]] = out.get(e["scope"], 0.0) + self_s
    n = max(len(windows), 1)
    return {k: v / n for k, v in out.items()}


def get(ctx) -> Optional[Dict[str, float]]:
    """{scope: device self seconds a traced round}, or None."""
    if "scopes" in ctx:
        return ctx["scopes"]
    ctx["scopes"] = None
    if not ctx.get("trace"):
        return None
    path = trace_reduce.find_xplane(os.path.join(
        BENCH, ".cache", ctx["cell"]["name"], "trace"))
    if path is None:
        return None
    prof = trace_reduce.read_profile(path)
    metadata = stage_reduce.read_op_metadata(path, want=("tf_op",))
    rounds = int(ctx["trace"]["rounds"])
    name, windows = stage_reduce.round_module(
        trace_reduce.load_device_events(
            prof, line_name=trace_reduce.MODULES_LINE), rounds)
    if name is None:
        return None
    ops = []
    for plane in prof.planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        table = metadata.get(plane.name, {})
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                ops.append({"lane": plane.name, "name": ev.name,
                            "start": ev.start_ns / 1e9,
                            "dur": ev.duration_ns / 1e9,
                            "scope": scope_of(
                                table.get(ev.name, {}).get("tf_op"))})
    red = {k: v / max(rounds, 1)
           for k, v in scope_seconds(ops, windows).items()}
    print("benchmark: model scope seconds a round: " + json.dumps(
        {k: round(v, 6) for k, v in sorted(red.items(),
                                           key=lambda kv: -kv[1])}),
        flush=True)
    ctx["scopes"] = red or None
    return ctx["scopes"]


def scope_s_per_round(ctx, scope: str) -> Optional[float]:
    red = get(ctx)
    return None if not red else red.get(scope)


def mixer_roofline_pct(ctx, scope: str, layer_kind: str, work: str
                       ) -> Optional[float]:
    """100 x the least time the chip could take for a round's calls of
    one mixer over the scope's device seconds. The calls: the round's
    sequences (k clients x K steps x B rows) times the configuration's
    layers of ``layer_kind`` ('linear' | 'full'); the work of a call:
    ``<work>_flops`` and ``<work>_bytes`` of ``flops/<arch>.py``, of the
    mathematics; the least time: the larger of FLOPs over the bf16 peak
    and bytes over the memory bandwidth of ``peaks.json``. None where
    the configuration's file counts no such work or the trace holds no
    such scope."""
    from . import runner

    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    seconds = scope_s_per_round(ctx, scope)
    if not seconds or not hasattr(flops, work + "_flops"):
        return None
    s = flops.spec()
    calls = ctx["samples_per_round"] * flops.layer_counts(s)[layer_kind]
    with open(os.path.join(BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"][ctx["device"]["kind"]]
    least = max(
        getattr(flops, work + "_flops")(s["seq_len"], s)
        / peak["bf16_flops_per_s"],
        getattr(flops, work + "_bytes")(s["seq_len"], s)
        / peak["hbm_bytes_per_s"])
    return 100.0 * calls * least / seconds
