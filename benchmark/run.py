#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

One run of one cell of ``BENCHMARK.json`` on the machine it is started
on; the last line of standard output is the result as one JSON object.
Exits non-zero, printing no result, without the cell's TPU chips or
without the program (``fedtorch_tpu``) beside ``benchmark/``.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    sys.path.insert(0, REPO)
    os.chdir(REPO)   # the launcher's default paths are relative
    try:
        import fedtorch_tpu  # noqa: F401
    except ImportError as e:
        sys.stderr.write(f"benchmark: the program is not beside "
                         f"benchmark/ ({e})\n")
        return 4
    from benchmark.harness import runner

    result = runner.run_cell(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
