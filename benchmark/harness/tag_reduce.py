"""Device seconds of the operations whose framework name holds a given
component, whatever ``lm.*`` scope they lie in.

``scope_reduce`` gives an operation to the innermost ``lm.<name>``
component of its framework name; a part of such a scope that is to be
read on its own carries a name without the ``lm.`` prefix
(``delta.inverse`` inside ``lm.delta_rule``: the chunk's triangular
inverse, forward, recomputed and its backward rule), so that the
scope's seconds keep counting it. The profile is read as
``scope_reduce`` reads it, self seconds inside the round module's
executions by ``scope_reduce.scope_seconds``; against a program without
such a component, or a run without a trace, the reader gets None.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional, Sequence, Tuple

from . import scope_reduce, stage_reduce, trace_reduce


def tag_seconds(ops: Sequence[dict],
                windows: Dict[str, List[Tuple[float, float]]],
                tag: str) -> Optional[float]:
    """Self seconds of the operations (``lane``, ``name``, ``start``,
    ``dur``, ``framework``) whose framework name holds ``tag``, inside
    ``windows`` (per lane), averaged over the lanes; None where no
    operation holds it."""
    labelled = [dict(o, scope=tag if tag in (o["framework"] or "")
                     else None) for o in ops]
    return scope_reduce.scope_seconds(labelled, windows).get(tag)


def tagged_s_per_round(ctx, tag: str) -> Optional[float]:
    """Device self seconds a traced round of the operations inside the
    round module whose framework name (``tf_op``) holds ``tag``."""
    if not ctx.get("trace"):
        return None
    path = trace_reduce.find_xplane(os.path.join(
        scope_reduce.BENCH, ".cache", ctx["cell"]["name"], "trace"))
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
                            "framework": table.get(ev.name, {}).get(
                                "tf_op")})
    seconds = tag_seconds(ops, windows, tag)
    return None if seconds is None else seconds / max(rounds, 1)
