#!/usr/bin/env python3
"""``run.py`` by hand, with every per-layer reader asked.

    python3 benchmark/all_readers.py --workload <cell> --seed <n> \
        --seconds <s> [--rows <file.jsonl.gz>]

A traced run of the cell as ``run.py --trace 1`` makes it, but for the
readers: every per-layer metric of ``BENCHMARK.json`` is asked, those
whose ``workloads`` list names other cells too (a list is widened by a
``benchmark`` PR only; until then a new cell's fold, stages, kept
products, save and evaluation are read this way). A reader that finds
nothing, or raises over a cell it was not written for, is left out and
named on standard error. ``--rows`` keeps the launcher's rows (gzip),
which hold the round's counters round by round. The result line has
the shape of ``run.py``'s. Run by hand on the chip, like ``probe.py``;
not run by the benchmark's own runs, and no reading of it is judged.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rows", default=None)
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    os.chdir(REPO)
    from benchmark.harness import runner

    listed, load, spans_of = (runner.metrics_for, runner.load_by_name,
                              runner.read_spans)

    def metrics_for(cell, group):
        return list(cell["bench"][group]) if group == "per_layer" \
            else listed(cell, group)

    def load_by_name(kind, name):
        mod = load(kind, name)
        if kind != "layer_metrics":
            return mod

        def read(ctx):
            try:
                value = mod.read(ctx)
            except Exception as e:   # a reader of another cell's layers
                value = None
                sys.stderr.write(f"all_readers: {name} raised {e!r}\n")
            if value is None:
                sys.stderr.write(f"all_readers: {name} reads nothing\n")
            return value
        return argparse.Namespace(read=read)

    def read_spans(run_dir):
        rows = os.path.join(run_dir, "metrics.jsonl")
        if args.rows and os.path.exists(rows):
            os.makedirs(os.path.dirname(os.path.abspath(args.rows)),
                        exist_ok=True)
            with open(rows, "rb") as f, gzip.open(args.rows, "wb") as g:
                shutil.copyfileobj(f, g)
        return spans_of(run_dir)

    runner.metrics_for, runner.load_by_name, runner.read_spans = \
        metrics_for, load_by_name, read_spans
    result = runner.run_cell(args.workload, args.seed, args.seconds, True,
                             t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
