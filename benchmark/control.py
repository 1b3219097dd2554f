#!/usr/bin/env python3
"""The control of ``correct``, and the readings its limits are set from.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 \
        [--sound-seeds 4,5,6] --seconds 5 \
        [--controls fp8,bf16_params,bf16_accum]

For each seed: one short run of the cell (the launcher's own loop, at
the cell's own size), compared with the float32 reference as every
benchmark run does; then, for ``--seeds``, each control, which is the
reference put in the program's place and computed below the precision
the configuration states, on the same parameters, cohorts and batches:

``fp8``          operands of every convolution and matrix product
                 rounded to float8 e4m3 (the configuration computes in
                 bfloat16);
``bf16_params``  parameters rounded to bfloat16 after every local and
                 server step (the configuration keeps them in float32);
``bf16_accum``   the server's sum of the clients' movements kept in
                 bfloat16 (the configuration aggregates in float32).

Prints one JSON line per seed with the program's numbers and each
control's, and a summary: the largest a sound run gave and the smallest
a control gave, per number. Not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=5.0)
    p.add_argument("--sound-seeds", default="",
                   help="more seeds of the program alone")
    p.add_argument("--controls", default="fp8,bf16_params,bf16_accum")
    args = p.parse_args(argv)
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    from benchmark.harness import correct, runner
    from benchmark.reference import _ops

    hooks = {"fp8": dict(cast=_ops.fp8_round_trip),
             "bf16_params": dict(param_cast=_ops.bf16_round_trip),
             "bf16_accum": dict(accum_cast=_ops.bf16_round_trip)}
    sound, controls = [], {c: [] for c in args.controls.split(",") if c}
    cell = runner.load_cell(args.workload)
    model = runner.load_by_name("reference", cell["config_file"]["arch"])
    ints = lambda text: [int(s) for s in text.split(",") if s]
    for seed in ints(args.seeds) + ints(args.sound_seeds):
        res = runner.run_cell(args.workload, seed, args.seconds, False,
                              keep_check=True)
        chk = res.pop("_check")
        line = {"seed": seed, "correct": res["correct"],
                "program": chk["numbers"],
                "metrics": {k: v["value"]
                            for k, v in res["metrics"].items()},
                "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        sound.append(chk["numbers"])
        for name in controls if seed in ints(args.seeds) else ():
            ctrl = correct.as_program(correct.reference_rounds(
                chk["case"], model.loss,
                cell["traffic_file"]["algorithm"], chk["hp"],
                **hooks[name]))
            nums = correct.compare(chk["case"], ctrl, chk["ref"])
            controls[name].append(nums)
            line[name] = nums
            line[name + "_correct"] = correct.verdict(
                nums, cell["config_file"]["correct"]["limits"])["correct"]
        print("control: " + json.dumps(line), flush=True)
    summary = {"sound_max": {k: max(n[k] for n in sound)
                             for k in sound[0]}}
    for name, runs in controls.items():
        summary[name + "_min"] = {k: min(n[k] for n in runs)
                                  for k in runs[0]}
    print("control summary: " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
