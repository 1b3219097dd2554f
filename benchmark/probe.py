#!/usr/bin/env python3
"""What ``correct`` costs at a model's size, before the model exists.

    python3 benchmark/probe.py [--params 928600000] [--leaves 200] \
        [--largest 48000000] [--clients 2] [--steps 2]

Seeded float32 trees of ``--params`` elements in ``--leaves`` leaves
(one of ``--largest``, the rest alike), and no model: a loss that reads
every element of every leaf once and costs next to nothing, so that
what is timed is the FedAvg reference's own arithmetic and its traffic
between host and device. First the program's side is made as a run's
callback leaves it (the reference's rounds, each moved a little
further: after the first and the last seeded round, and around the
late one). Then ``correct.compare`` beside ``correct.reference_rounds``
as ``correct.check`` runs them: three seeded rounds and a late one,
each compared as it comes, its trees let go of once read. Prints the
seconds of each part, the device's ``peak_bytes_in_use`` and the
host's peak resident size, as one JSON line. Run by hand on the chip,
like ``control.py``; not run by the benchmark's own runs.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def loss(params, x, y, cast):
    """Half the squared distance of every parameter from the batch's
    mean: a gradient is as large as the parameters and every leaf
    moves."""
    import jax
    import jax.numpy as jnp

    centre = jnp.mean(x) + jnp.mean(y)
    return sum(0.5 * jnp.sum(jnp.square(cast(p) - centre))
               for p in jax.tree.leaves(params))


def main(argv=None) -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--params", type=int, default=928_600_000)
    p.add_argument("--leaves", type=int, default=200)
    p.add_argument("--largest", type=int, default=48_000_000)
    p.add_argument("--clients", type=int, default=2)
    p.add_argument("--steps", type=int, default=2)
    p.add_argument("--seed", type=int, default=1)
    args = p.parse_args(argv)
    sys.path.insert(0, REPO)
    os.chdir(REPO)
    import jax
    import numpy as np

    from benchmark.harness import correct

    rng = np.random.default_rng(args.seed)
    rest = (args.params - args.largest) // max(args.leaves - 1, 1)
    sizes = [args.largest] + [rest] * (args.leaves - 1)
    sizes[-1] += args.params - sum(sizes)
    clock, seconds = time.perf_counter, {}

    t0 = clock()
    p0 = {f"leaf{i:03d}": rng.random(n, np.float32) - np.float32(0.5)
          for i, n in enumerate(sizes)}
    late0 = {k: -v for k, v in p0.items()}
    seconds["seeded_trees"] = clock() - t0

    def round_inputs(r):
        xs = rng.standard_normal((args.clients, args.steps, 4, 8))
        ys = rng.standard_normal((args.clients, args.steps, 4))
        return (list(range(r, r + args.clients)), xs.astype(np.float32),
                ys.astype(np.float32))

    case = {"p0": p0, "rounds": [round_inputs(r) for r in range(3)],
            "late": {"p": late0, "state": None, "round": round_inputs(7)}}
    hp = {"lr": 0.1, "weight_decay": 1e-4, "server_lr": 1.0,
          "local_steps": args.steps}
    n = len(case["rounds"])

    def further(end, start):
        return {k: end[k] + np.float32(0.05) * (end[k] - start[k])
                for k in end}

    t0 = clock()
    prog = {"params": [], "losses": []}
    for i, (tree, value) in enumerate(
            correct.reference_rounds(case, loss, "fedavg", hp)):
        if i in (0, n - 1):
            prog["params"].append(further(tree, p0))
        if i < n:
            prog["losses"].append(value * 1.001)
        else:
            prog.update(late_params=further(tree, late0),
                        late_loss=value * 1.001)
        del tree
    seconds["program_stand_ins"] = clock() - t0
    del p0

    t0 = clock()
    ref = correct.Rounds(correct.reference_rounds(case, loss, "fedavg", hp))
    numbers = correct.compare(case, prog, ref, release=True)
    seconds["reference_4_rounds"] = ref.seconds
    seconds["compare"] = clock() - t0 - ref.seconds
    dev = jax.devices()[0]
    print("probe: " + json.dumps({
        "params": sum(sizes), "leaves": len(sizes), "largest": max(sizes),
        "clients": args.clients, "steps": args.steps,
        "seconds": {k: round(v, 3) for k, v in seconds.items()},
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "peak_bytes_in_use": (dev.memory_stats() or {}).get(
                       "peak_bytes_in_use")},
        "host_peak_rss_bytes": resource.getrusage(
            resource.RUSAGE_SELF).ru_maxrss * 1024,
        "host_cpus": os.cpu_count(),
        "numbers": numbers}), flush=True)
    print(subprocess.run(["free", "-g"], capture_output=True,
                         text=True).stdout, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
