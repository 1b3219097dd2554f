"""How ``correct`` is decided: rounds of the launcher's own loop against
the plain float32 reference.

Two stretches of the same compiled round program and state are
followed. The callback copies the server's parameters to the host
around each; after the run the reference
(``benchmark/reference/<arch>.py`` and ``<algorithm>.py``, which import
nothing of the program) repeats them in float32 at the highest matmul
precision on the same cohorts and batches.

*Rounds 0-2, from the seed.* The reference starts from the same seeded
initial parameters and follows all three on its own.

*The round after the window* (the launcher's drain round). The
reference starts from the server's parameters as the window left them
(and, where the algorithm keeps any, the cohort's client state): one
round at a trained point, where the model is far better conditioned
than at its seeded weights.

Why both: ResNet-20 with batch-statistics normalization at lr 0.1 is
chaotic from its seeded weights (a 1e-6 perturbation of the parameters
is 0.4 % of the first gradient and 80 % of the movement after ten
steps, on the CPU in float32), so from the seed only the first loss is
steady; the trained point carries the update's comparison.

Numbers compared, each against a limit of its own in the
configuration's file (``PERF.md`` has the readings they were set from).
The judged numbers are the keys of the file's ``limits``; every other
number is printed beside them and held to none:

``loss_r0_rel``          round 0's mean client loss, relative gap: there
                         to catch a part of the batch or of the cohort
                         left out;
``change_norm_gap``      the parameters' change over rounds 0-2: gap
                         between the program's norm and the reference's
                         over all leaves together; fails float8
                         operands, and a step that returns its state
                         unchanged reads 1;
``loss_late_rel``        the late round's mean client loss, relative
                         gap: fails float8 operands at the trained point;
``update_late_cos_gap``  1 - cosine between the program's late update
                         and the reference's over all leaves: a wrong
                         direction reads 1 or more;
``change_leaf_ratio`` /  worst leaf of |ln(program's norm / reference's
``late_leaf_ratio``      norm)| of the rounds 0-2 change and of the late
                         update: a leaf moved by the wrong amount,
                         whatever its size;
``frozen_leaves``        leaves the program left bit-identical over
                         rounds 0-2 or over the late round while the
                         reference moved them (exact: limit 0);
``param_dtype_mismatch`` leaves of the live server parameters and client
                         state whose type is not the one the
                         configuration's ``precision.parameters`` states
                         (exact: limit 0);
``params_bf16_grid_gap`` share of the late parameters' elements that lie
                         on bfloat16's grid, program minus reference:
                         parameters held or rounded below float32;
``update_bf16_grid_gap`` the same share of the late update's elements:
                         an aggregation accumulated below float32.

The last three are what a precision below the stated float32 of the
parameters fails. A trajectory cannot show it: the operands of every
product are rounded to bfloat16 anyway, the rounds are chaotic, and what
bfloat16 parameters lose (updates under half a unit of the last place)
shows only over many rounds. The values themselves do show it, exactly
and at every seed.
"""
from __future__ import annotations

import math
import statistics
import time
from typing import Dict, List

import numpy as np


BLOCK = 1 << 16     # elements of a leaf converted at a time: 0.5 MiB a buffer
SUMS = ("pp", "rr", "dot", "dd")
GRIDS = ("params_p", "params_r", "update_p", "update_r")


class _Scratch:
    """One block's buffers, allocated once for a whole comparison."""

    def __init__(self, n: int):
        self.z, self.a, self.b, self.d, self.t = (
            np.empty(n, np.float64) for _ in range(5))
        self.x32 = np.empty(n, np.float32)
        self.bits = np.empty(n, np.uint32)
        self.m, self.g = np.empty(n, bool), np.empty(n, bool)

    def on_grid(self, x32: np.ndarray) -> np.ndarray:
        """Flags of the float32 elements within 1/32 of bfloat16's
        spacing of a bfloat16 value: the low 16 bits of the pattern
        under 2048 or over 65536 - 2048."""
        bits, g = self.bits[:x32.size], self.g[:x32.size]
        np.add(x32.view(np.uint32), 2047, out=bits)
        np.bitwise_and(bits, 0xFFFF, out=bits)
        return np.less(bits, 4095, out=g)

    def counts(self, x32: np.ndarray, m: np.ndarray) -> np.ndarray:
        """[on the grid, counted] of the elements of ``x32`` that ``m``
        flags."""
        g = self.on_grid(x32)
        g &= m
        return np.array([np.count_nonzero(g), np.count_nonzero(m)])

    def params_grid(self, x32: np.ndarray):
        """[on the grid, counted] of a block of parameters as float32:
        the elements that are finite and not zero."""
        m, g = self.m[:x32.size], self.g[:x32.size]
        np.isfinite(x32, out=m)
        np.not_equal(x32, 0.0, out=g)
        m &= g
        return self.counts(x32, m)

    def update_grid(self, u: np.ndarray, x: np.ndarray):
        """[on the grid, counted] of a block of an update ``u`` of the
        parameters ``x`` (both float64; ``x`` is overwritten): the
        elements that moved by 2**-10 of their size or more, since a
        smaller movement of a float32 parameter has too few bits of its
        own to tell."""
        m, g, t = self.m[:u.size], self.g[:u.size], self.t[:u.size]
        np.abs(x, out=x)
        x *= 2.0 ** -10
        np.abs(u, out=t)
        np.greater_equal(t, x, out=m)
        np.not_equal(u, 0.0, out=g)
        m &= g
        return self.counts(_load(self.x32[:u.size], u), m)


def _flat(tree) -> List[np.ndarray]:
    import jax

    return [np.asarray(x).reshape(-1) for x in jax.tree.leaves(tree)]


def _load(dst: np.ndarray, src: np.ndarray) -> np.ndarray:
    np.copyto(dst, src, casting="unsafe")
    return dst


def leaf_sums(start, prog, ref, grid: bool = False) -> dict:
    """One walk over the leaves of ``start`` and of the program's and
    the reference's tree that moved from it: per leaf, ``pp`` and
    ``rr`` (the squared norms of the program's and of the reference's
    movement), ``dot`` (their dot product) and ``dd`` (the squared norm
    of program minus reference), all in float64. With ``grid`` also,
    over all leaves, the counts ``[on bfloat16's grid, counted]`` of
    each side's parameters (``params_p``, ``params_r``) and movement
    (``update_p``, ``update_r``). Each leaf is read once, a block at a
    time, through one scratch. One thread: on the chip's host four
    threads took 2.5 times as long (PERF.md section 6, PR 31)."""
    s = _Scratch(BLOCK)
    out = dict({k: [] for k in SUMS},
               **{k: np.zeros(2, np.int64) for k in GRIDS if grid})
    for base, pl, rl in zip(_flat(start), _flat(prog), _flat(ref)):
        sums = dict.fromkeys(SUMS, 0.0)
        for lo in range(0, base.size, BLOCK):
            m = min(BLOCK, base.size - lo)
            z = _load(s.z[:m], base[lo:lo + m])
            pa, pb = pl[lo:lo + m], rl[lo:lo + m]
            a, b = _load(s.a[:m], pa), _load(s.b[:m], pb)
            d = np.subtract(a, b, out=s.d[:m])
            sums["dd"] += float(np.dot(d, d))
            for x, src, side in ((a, pa, "p"), (b, pb, "r")):
                if grid:
                    out["params_" + side] += s.params_grid(
                        src if src.dtype == np.float32
                        else _load(s.x32[:m], src))
                    np.copyto(d, x)   # the parameters, for the update's
                x -= z
                if grid:
                    out["update_" + side] += s.update_grid(x, d)
            sums["pp"] += float(np.dot(a, a))
            sums["rr"] += float(np.dot(b, b))
            sums["dot"] += float(np.dot(a, b))
        for k in SUMS:
            out[k].append(sums[k])
    return out


def norms(squares: List[float]) -> List[float]:
    return [math.sqrt(v) for v in squares]


def worst_leaf_norm_gap(pn: List[float], rn: List[float]) -> float:
    """Worst leaf of |program's norm - reference's| over max(the
    reference's norm of the leaf, of the median leaf)."""
    med = statistics.median(rn)
    return max(abs(p - r) / max(r, med, 1e-30) for p, r in zip(pn, rn))


def worst_leaf_ratio(pn: List[float], rn: List[float]) -> float:
    """Worst leaf of |ln(program's norm / reference's)|, leaves the
    reference does not move left out; 99 where the program's is 0."""
    worst = 0.0
    for p, r in zip(pn, rn):
        if r > 0.0:
            worst = max(worst, abs(math.log(p / r)) if p > 0.0 else 99.0)
    return worst


def frozen_leaves(pn: List[float], rn: List[float]) -> int:
    return sum(1 for p, r in zip(pn, rn) if p == 0.0 and r > 0.0)


def global_norm_gap(pn: List[float], rn: List[float]) -> float:
    p = sum(v * v for v in pn) ** 0.5
    r = sum(v * v for v in rn) ** 0.5
    return abs(p - r) / max(r, 1e-30)


def rel_l2(sums: dict) -> float:
    """‖program - reference‖ over the reference's movement, all
    leaves."""
    return (sum(sums["dd"]) / max(sum(sums["rr"]), 1e-60)) ** 0.5


def cos_gap(sums: dict) -> float:
    """1 - cosine between the two movements, all leaves."""
    return 1.0 - sum(sums["dot"]) / max(
        (sum(sums["pp"]) * sum(sums["rr"])) ** 0.5, 1e-60)


def grid_share(count) -> float:
    """Share of the counted elements on bfloat16's grid: 1/16 for
    values that carry float32's mantissa, 1 for values rounded to
    bfloat16."""
    on, n = (int(c) for c in count)
    return on / n if n else 0.0


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def compare(case: dict, prog: dict, ref, release: bool = False
            ) -> Dict[str, float]:
    """``prog``: {"params": [after the first seeded round, ..., after
    the last], "losses": [each seeded round's], "late_params": after
    the late round, "late_loss"}, and it may carry "dtype_mismatch"
    (the live state's count). ``ref``: the reference's rounds in order,
    the seeded ones and then the late one, each (the server's
    parameters after it, its loss): a list, or ``reference_rounds``
    itself, which then runs a round when the comparison asks for it.

    A round's trees are walked as it comes, leaf by leaf
    (``leaf_sums``), and every number is formed from the per-leaf sums.
    With ``release`` the seeded stretch's trees are taken out of
    ``case`` and ``prog`` once read, so that beside a generator at most
    six trees are on the host at any time (the seeded start, the
    program's last seeded round, the late round's two, the reference's
    round and the one it started from), where all at once are nine."""
    n, losses = len(prog["losses"]), []
    for i, (tree, loss) in enumerate(ref):
        losses.append(loss)
        if i == 0:
            first = leaf_sums(case["p0"], prog["params"][0], tree)
            if release and n > 1:
                prog["params"][0] = None
        if i == n - 1:
            change = first if n == 1 else leaf_sums(
                case["p0"], prog["params"][-1], tree)
            if release:
                del case["p0"], prog["params"][:]
        if i == n:
            late = leaf_sums(case["late"]["p"], prog["late_params"], tree,
                             grid=True)
        del tree
    first_n, change_n, late_n = (
        (norms(s["pp"]), norms(s["rr"])) for s in (first, change, late))
    return {
        "loss_r0_rel": rel_gap(prog["losses"][0], losses[0]),
        "change_norm_gap": global_norm_gap(*change_n),
        "loss_late_rel": rel_gap(prog["late_loss"], losses[n]),
        "update_late_cos_gap": cos_gap(late),
        "change_leaf_ratio": worst_leaf_ratio(*change_n),
        "late_leaf_ratio": worst_leaf_ratio(*late_n),
        "frozen_leaves": float(frozen_leaves(*change_n)
                               + frozen_leaves(*late_n)),
        "param_dtype_mismatch": float(prog.get("dtype_mismatch", 0)),
        "params_bf16_grid_gap": grid_share(late["params_p"])
        - grid_share(late["params_r"]),
        "update_bf16_grid_gap": grid_share(late["update_p"])
        - grid_share(late["update_r"]),
        # the contract's per-leaf numbers and the relative L2, chaotic
        # in this cell (PERF.md section 2)
        "grad_norm_gap": worst_leaf_norm_gap(*first_n),
        "update_late_rel_l2": rel_l2(late),
        "update_r0_rel_l2": rel_l2(first),
        "change_worst_leaf_gap": worst_leaf_norm_gap(*change_n),
        "late_worst_leaf_gap": worst_leaf_norm_gap(*late_n),
        "loss_r012_rel": max(rel_gap(a, b) for a, b in
                             zip(prog["losses"], losses)),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each judged number (a key of ``limits``) beside its limit:
    ``lines`` for the run's log, ``compared`` for its result line (a
    number that is not finite goes there as text, which JSON can
    hold)."""
    lines, compared, ok = [], {}, True
    for name, limit in limits.items():
        value = float(numbers[name])
        good = bool(math.isfinite(value) and value <= limit)
        ok = ok and good
        lines.append(f"{name} = {value:.6g} (limit {limit:g})"
                     f" {'ok' if good else 'FAILED'}")
        compared[name] = {"value": value if math.isfinite(value)
                          else repr(value), "limit": limit}
    lines.append("not judged: " + ", ".join(
        f"{k} = {v:.4g}" for k, v in numbers.items() if k not in limits))
    return {"correct": ok, "lines": lines, "numbers": numbers,
            "compared": compared}


# -- the two sides ---------------------------------------------------------

def gather_case(cfg, trainer, late: dict, rounds: int = 3) -> dict:
    """The reference's inputs: initial parameters, each seeded round's
    cohort and batches, and the late round's with the parameters (and
    client state) it started from; all on the host, so that the trainer
    and its store can go before the reference runs."""
    import jax

    from . import inputs

    rng, p0 = inputs.seed_keys(trainer, cfg.train.manual_seed)
    return {"p0": jax.device_get(p0),
            "rounds": [inputs.round_inputs(trainer, rng, r)
                       for r in range(rounds)],
            "late": {"p": late["before"], "state": late.get("state"),
                     "round": inputs.round_inputs(trainer, rng,
                                                  late["round"])}}


def hyper(cfg) -> dict:
    if cfg.optim.in_momentum or cfg.optim.out_momentum \
            or cfg.optim.optimizer != "sgd" \
            or cfg.lr_schedule.schedule_scheme is not None:
        raise NotImplementedError(
            "the plain reference is SGD without momentum at a constant lr")
    return {"lr": cfg.optim.lr, "weight_decay": cfg.optim.weight_decay,
            "server_lr": cfg.optim.lr_scale_at_sync,
            "local_steps": max(cfg.train.local_step, 1),
            "quantized_bits": cfg.federated.quantized_bits
            if cfg.federated.quantized else 0}


def reference_rounds(case: dict, loss_fn, algorithm: str, hp: dict,
                     cast=None, param_cast=None, accum_cast=None):
    """Follow the case with the plain reference, a round at each
    ``next``: the seeded rounds and then the late one, each as (the
    server's parameters after it, host arrays; its loss). ``loss_fn``
    is the model's, ``reference/<arch>.py:loss``. ``cast``,
    ``param_cast`` and ``accum_cast`` are the control's hooks (operands
    of every product; parameters after every update; the server's
    running sum of the clients' movements); identity for the
    reference. Of its own trees it holds the last round's alone, which
    the next round starts from."""
    import jax
    import jax.numpy as jnp

    from . import runner
    from ..reference import _ops

    run_round = runner.load_by_name("reference", algorithm).make_round(
        loss_fn, hp, cast or _ops.identity, param_cast or _ops.identity,
        accum_cast or _ops.identity)

    def one(server, state, cohort, xs, ys):
        # the server's parameters go in and come back as host arrays:
        # the algorithm's file decides what of them the device holds
        with jax.default_matmul_precision("highest"):
            server, state, loss = run_round(
                server, state, cohort, jnp.asarray(xs), jnp.asarray(ys))
        return server, state, float(loss)

    server, state = case["p0"], None
    for rnd in case["rounds"]:
        server, state, loss = one(server, state, *rnd)
        yield server, loss
    del server
    late = case["late"]
    server, _, loss = one(late["p"], late["state"], *late["round"])
    yield server, loss


def as_program(rounds) -> dict:
    """A side's rounds in the form of the program's side of
    ``compare``: a control put in the program's place."""
    *seeded, (late, late_loss) = rounds
    return {"params": [seeded[0][0], seeded[-1][0]],
            "losses": [loss for _, loss in seeded],
            "late_params": late, "late_loss": late_loss}


class Rounds:
    """A side's rounds as they come, with the seconds spent waiting for
    them and the losses they gave."""

    def __init__(self, rounds):
        self.rounds, self.seconds, self.losses = iter(rounds), 0.0, []

    def __iter__(self):
        return self

    def __next__(self):
        t0 = time.perf_counter()
        try:
            tree, loss = next(self.rounds)
        finally:
            self.seconds += time.perf_counter() - t0
        self.losses.append(loss)
        return tree, loss


def dtype_mismatch(trees, stated: str) -> int:
    """Floating leaves of the live state whose type is not ``stated``."""
    import jax
    import jax.numpy as jnp

    return sum(1 for x in jax.tree.leaves(trees)
               if jnp.issubdtype(x.dtype, jnp.floating)
               and x.dtype != jnp.dtype(stated))


def check(cell: dict, cfg, case: dict, params_after: dict, late: dict,
          rows: List[dict], keep: bool = False) -> dict:
    """The run's verdict. ``case``: what ``gather_case`` rebuilt;
    ``params_after``: {round: host parameters} of the first and the
    last of the seeded rounds followed; ``late``: {"round", "before",
    "after", "state"} of the round after the window; both as the
    loop's callback copied them. The reference runs a round as the
    comparison asks for it, and ``case`` and ``params_after`` lose
    their trees as they are read; with ``keep`` (the control script,
    the tests) nothing is let go of and the verdict carries the case
    and both sides."""
    from . import runner

    cfgf, tf = cell["config_file"], cell["traffic_file"]
    n = len(case["rounds"])
    by_round = {r["round"]: r for r in rows}
    after = dict(params_after) if keep else params_after
    prog = {"params": [after.pop(r) for r in sorted(after)],
            "losses": [by_round[r]["loss"] for r in range(n)],
            "late_params": late["after"],
            "late_loss": by_round[late["round"]]["loss"],
            "dtype_mismatch": late["dtype_mismatch"]}
    hp = hyper(cfg)
    t0 = time.perf_counter()
    ref = Rounds(reference_rounds(
        case, runner.load_by_name("reference", cfgf["arch"]).loss,
        tf["algorithm"], hp))
    kept = list(ref) if keep else None
    out = verdict(compare(case, prog, kept or ref, release=not keep),
                  cfgf["correct"]["limits"])
    out["seconds"] = {"reference": ref.seconds, "compare":
                      time.perf_counter() - t0 - ref.seconds}
    out["lines"].insert(0, f"losses program {prog['losses']} late "
                        f"{prog['late_loss']}; reference "
                        f"{ref.losses[:n]} late {ref.losses[n]}")
    if keep:
        out["case"], out["prog"], out["ref"], out["hp"] = \
            case, prog, kept, hp
    return out
