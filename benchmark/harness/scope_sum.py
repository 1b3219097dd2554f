"""Several of ``scope_reduce``'s model scopes read as one piece of the
model, and that piece's share of its roofline.

A looped model's stack of layers is three scopes in the device trace
(``lm.loop`` for the scan over passes and what it holds outside the two
others, ``lm.attention``, ``lm.mlp``) and its exits two (``lm.exit``,
``lm.head``), because ``scope_reduce`` gives an operation to the
innermost ``lm.*`` component of its name. Against a program without
such scopes, or a run without a trace, every reader gets None.
"""
from __future__ import annotations

import json
import os
from typing import Optional, Sequence

from . import scope_reduce

LOOP_STACK = ("lm.loop", "lm.attention", "lm.mlp")
EXITS = ("lm.exit", "lm.head")


def seconds_per_round(ctx, scopes: Sequence[str]) -> Optional[float]:
    """Device self seconds a traced round under ``scopes`` together;
    None unless the first of them (the piece's own scope) is there."""
    red = scope_reduce.get(ctx)
    if not red or scopes[0] not in red:
        return None
    return sum(red.get(s, 0.0) for s in scopes)


def roofline_pct(ctx, scopes: Sequence[str], work: str) -> Optional[float]:
    """100 x the least time the chip could take for a round's sequences
    (k clients x K steps x B rows) of ``<work>_flops`` and
    ``<work>_bytes`` of ``flops/<arch>.py`` (one sequence's calls, of
    the mathematics) over the scopes' device seconds: the larger of
    FLOPs over the bf16 peak and bytes over the memory bandwidth of
    ``peaks.json``."""
    from . import runner

    flops = runner.load_by_name("flops", ctx["cell"]["config_file"]["arch"])
    seconds = seconds_per_round(ctx, scopes)
    if not seconds or not hasattr(flops, work + "_flops"):
        return None
    s = flops.spec()
    with open(os.path.join(scope_reduce.BENCH, "peaks.json")) as f:
        peak = json.load(f)["devices"][ctx["device"]["kind"]]
    least = max(
        getattr(flops, work + "_flops")(s["seq_len"], s)
        / peak["bf16_flops_per_s"],
        getattr(flops, work + "_bytes")(s["seq_len"], s)
        / peak["hbm_bytes_per_s"])
    return 100.0 * ctx["samples_per_round"] * least / seconds
