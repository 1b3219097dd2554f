#!/usr/bin/env python3
"""``control.py`` for a configuration too large to keep every tree:
the same controls of ``correct``, one seed a process run, in the memory
a benchmark run has.

    python3 benchmark/control_big.py --workload <cell> --seed <n> \
        --seconds 5 [--controls fp8,bf16_params,bf16_accum]

``control.py`` holds the case, the program's four trees and the
reference's four, and then each control's four beside them: at
928.9 M parameters that is 48 GB of a host that has 45. Here the seed's
one run of the cell is judged as every benchmark run is (the reference
a round at a time, trees let go of once read), and then each control
(the reference put in the program's place, computed below the
precision the configuration states: ``control.py`` has the list) is
followed **in step with the reference**, a round of each at a time,
through ``correct.compare`` itself: six model-sized trees on the host
at most (the seeded start, the late round's start, and each side's
last round with the one it started from). The comparison's formulas,
the reference and the hooks are the ones every run uses; nothing of
them is edited or copied.

Prints ``control: {...}`` with the program's judged numbers and each
control's, and whether each comes out correct. By hand, on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Pulled(list):
    """``prog["params"]`` of ``compare`` for a side that is still
    running: the first and the last seeded round's trees, each made
    when the comparison reaches for it."""

    def __init__(self, side, n):
        super().__init__([None, None])
        self.side, self.n = side, n

    def __getitem__(self, i):
        if isinstance(i, slice):
            return super().__getitem__(i)
        self.side.until(0 if i == 0 else self.n - 1)
        return self.side.tree


class _Side(dict):
    """A control in the program's place of ``compare``, a round at a
    time: holds its newest tree alone."""

    def __init__(self, rounds, n):
        super().__init__()
        self.rounds, self.n = iter(rounds), n
        self.at, self.tree, self.losses = -1, None, []
        self["params"] = _Pulled(self, n)
        self["losses"] = _Losses(self, n)

    def until(self, i):
        while self.at < i:
            self.tree, loss = next(self.rounds)
            self.losses.append(loss)
            self.at += 1

    def __getitem__(self, key):
        if key == "late_params":
            self.until(self.n)
            return self.tree
        if key == "late_loss":
            self.until(self.n)
            return self.losses[self.n]
        return super().__getitem__(key)


class _Losses:
    def __init__(self, side, n):
        self.side, self.n = side, n

    def __len__(self):
        return self.n

    def __getitem__(self, i):
        self.side.until(i)
        return self.side.losses[i]

    def __iter__(self):
        self.side.until(self.n - 1)
        return iter(self.side.losses[:self.n])


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--controls", default="fp8,bf16_params,bf16_accum")
    args = p.parse_args(argv)
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    from benchmark.harness import correct, runner
    from benchmark.reference import _ops

    hooks = {"fp8": dict(cast=_ops.fp8_round_trip),
             "bf16_params": dict(param_cast=_ops.bf16_round_trip),
             "bf16_accum": dict(accum_cast=_ops.bf16_round_trip)}
    cell = runner.load_cell(args.workload)
    limits = cell["config_file"]["correct"]["limits"]
    model = runner.load_by_name("reference", cell["config_file"]["arch"])
    kept = {}
    check = correct.check

    def check_and_keep(cell_, cfg, case, params_after, late, rows, keep):
        # the run's own verdict on copies of the two dicts, so that the
        # seeded start and the late round's start outlive it
        kept.update(case=case, cfg=cfg)
        return check(cell_, cfg, dict(case), params_after, late, rows,
                     keep=keep)

    correct.check = check_and_keep
    try:
        res = runner.run_cell(args.workload, args.seed, args.seconds,
                              False)
    finally:
        correct.check = check
    line = {"seed": args.seed, "correct": res["correct"],
            "program": {k: v["value"] for k, v in res["compared"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
    case, hp = kept["case"], correct.hyper(kept["cfg"])
    algorithm = cell["traffic_file"]["algorithm"]
    n = len(case["rounds"])
    for name in [c for c in args.controls.split(",") if c]:
        side = _Side(correct.reference_rounds(
            case, model.loss, algorithm, hp, **hooks[name]), n)
        nums = correct.compare(dict(case), side, correct.reference_rounds(
            case, model.loss, algorithm, hp))
        line[name] = {k: nums[k] for k in limits}
        line[name + "_correct"] = correct.verdict(nums, limits)["correct"]
        print(f"control: {name} " + json.dumps(line[name]), flush=True)
    print("control: " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
