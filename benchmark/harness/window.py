"""Window arithmetic: which rounds are measured, and the statistics
over them. Pure functions of the callback's stamps and the launcher's
metrics rows, so they can be tested on a synthetic list.

A cycle is ``eval_freq`` rounds: the launcher evaluates and checkpoints
after its last round, and the callback's stamp of that round closes it.
The first whole cycles that hold ten rounds are set-up. The window is the whole
cycles after them, up to the first cycle that closes ``seconds`` or more
after the window opened.
"""
from __future__ import annotations

import math
from typing import List, Optional


def is_boundary(r: int, eval_freq: int) -> bool:
    return (r + 1) % eval_freq == 0


def warmup_rounds(eval_freq: int, least: int) -> int:
    """Rounds of the fewest whole cycles that hold ``least`` rounds."""
    return max(1, math.ceil(least / eval_freq)) * eval_freq


def closes_window(r: int, stamp: float, open_stamp: float, seconds: float,
                  eval_freq: int) -> bool:
    """True at the callback of the round that ends the window."""
    return is_boundary(r, eval_freq) and stamp - open_stamp >= seconds


def train_iteration_walls(stamps: dict, first: int, last: int,
                          eval_freq: int) -> List[float]:
    """Callback-to-callback wall of the window's iterations that hold a
    train round and nothing else (no evaluation, no checkpoint)."""
    return [stamps[r] - stamps[r - 1] for r in range(first, last + 1)
            if not is_boundary(r, eval_freq)
            and r in stamps and r - 1 in stamps]


def cycle_walls(stamps: dict, open_round: int, last: int,
                eval_freq: int) -> List[float]:
    """Wall of each whole cycle of the window."""
    ends = list(range(open_round, last + 1, eval_freq))
    return [stamps[b] - stamps[a] for a, b in zip(ends, ends[1:])]


def window_rows(rows: List[dict], first: int, last: int) -> List[dict]:
    return [r for r in rows if first <= r["round"] <= last]


def samples_per_s_chip(n_rounds: int, samples_per_round: int,
                       window_s: float, chips: int) -> float:
    return n_rounds * samples_per_round / window_s / chips


def host_gap_s_per_round(stamps: dict, rows: List[dict], first: int,
                         last: int) -> Optional[float]:
    """Mean over the window's rounds of callback-to-callback wall minus
    the round, evaluation and checkpoint the launcher timed itself."""
    gaps = []
    by_round = {r["round"]: r for r in rows}
    for r in range(first, last + 1):
        if r - 1 not in stamps or r not in stamps or r not in by_round:
            continue
        row = by_round[r]
        timed = row["round_s"] + row.get("eval_s", 0.0) \
            + row.get("checkpoint_s", 0.0)
        gaps.append(stamps[r] - stamps[r - 1] - timed)
    return sum(gaps) / len(gaps) if gaps else None
