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
from typing import Dict, List

import numpy as np


def _leaves(tree) -> List[np.ndarray]:
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def leaf_norms(p0, p1) -> List[float]:
    return [float(np.linalg.norm(a - z))
            for a, z in zip(_leaves(p1), _leaves(p0))]


def worst_leaf_norm_gap(p0, prog, ref) -> float:
    """Worst leaf of |‖prog - p0‖ - ‖ref - p0‖| over max(‖ref - p0‖ of
    the leaf, the median leaf's ‖ref - p0‖)."""
    pn, rn = leaf_norms(p0, prog), leaf_norms(p0, ref)
    med = statistics.median(rn)
    return max(abs(p - r) / max(r, med, 1e-30) for p, r in zip(pn, rn))


def worst_leaf_ratio(p0, prog, ref) -> float:
    """Worst leaf of |ln(‖prog - p0‖ / ‖ref - p0‖)|, leaves the
    reference does not move left out; 99 where the program's is 0."""
    worst = 0.0
    for p, r in zip(leaf_norms(p0, prog), leaf_norms(p0, ref)):
        if r > 0.0:
            worst = max(worst, abs(math.log(p / r)) if p > 0.0 else 99.0)
    return worst


def frozen_leaves(p0, prog, ref) -> int:
    return sum(1 for p, r in zip(leaf_norms(p0, prog), leaf_norms(p0, ref))
               if p == 0.0 and r > 0.0)


def global_norm_gap(p0, prog, ref) -> float:
    pn = sum(v * v for v in leaf_norms(p0, prog)) ** 0.5
    rn = sum(v * v for v in leaf_norms(p0, ref)) ** 0.5
    return abs(pn - rn) / max(rn, 1e-30)


def rel_l2(p0, prog, ref) -> float:
    num = den = 0.0
    for a, b, z in zip(_leaves(prog), _leaves(ref), _leaves(p0)):
        num += float(np.sum(np.square(a - b)))
        den += float(np.sum(np.square(b - z)))
    return (num / max(den, 1e-60)) ** 0.5


def cos_gap(p0, prog, ref) -> float:
    """1 - cosine between (prog - p0) and (ref - p0), all leaves."""
    dot = pp = rr = 0.0
    for a, b, z in zip(_leaves(prog), _leaves(ref), _leaves(p0)):
        dot += float(np.sum((a - z) * (b - z)))
        pp += float(np.sum(np.square(a - z)))
        rr += float(np.sum(np.square(b - z)))
    return 1.0 - dot / max((pp * rr) ** 0.5, 1e-60)


def bf16_grid_share(values: List[np.ndarray]) -> float:
    """Share of the elements (as float32) within 1/32 of bfloat16's
    spacing of a bfloat16 value: 1/16 for values that carry float32's
    mantissa, 1 for values rounded to bfloat16."""
    on = n = 0
    for v in values:
        low = np.ascontiguousarray(v, np.float32).view(np.uint32) & 0xFFFF
        on += int(np.count_nonzero(np.minimum(low, 65536 - low) < 2048))
        n += low.size
    return on / n if n else 0.0


def params_grid_share(params) -> float:
    return bf16_grid_share([x[(x != 0.0) & np.isfinite(x)]
                            for x in _leaves(params)])


def update_grid_share(p0, p1) -> float:
    """The grid share of (p1 - p0), over the elements that moved by
    2**-10 of their size or more: a smaller movement of a float32
    parameter has too few bits of its own to tell."""
    picked = []
    for a, z in zip(_leaves(p1), _leaves(p0)):
        u = a - z
        picked.append(u[(np.abs(u) >= np.abs(a) * 2.0 ** -10)
                        & (u != 0.0)])
    return bf16_grid_share(picked)


def rel_gap(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-30)


def compare(case: dict, prog: dict, ref: dict) -> Dict[str, float]:
    """``prog`` / ``ref``: {"params": [after round 0, 1, 2], "losses":
    [round 0, 1, 2], "late_params": after the late round, "late_loss"};
    ``prog`` may carry "dtype_mismatch" (the live state's count)."""
    p0, late0 = case["p0"], case["late"]["p"]
    p_late, r_late = prog["late_params"], ref["late_params"]
    return {
        "loss_r0_rel": rel_gap(prog["losses"][0], ref["losses"][0]),
        "change_norm_gap": global_norm_gap(p0, prog["params"][-1],
                                           ref["params"][-1]),
        "loss_late_rel": rel_gap(prog["late_loss"], ref["late_loss"]),
        "update_late_cos_gap": cos_gap(late0, p_late, r_late),
        "change_leaf_ratio": worst_leaf_ratio(p0, prog["params"][-1],
                                              ref["params"][-1]),
        "late_leaf_ratio": worst_leaf_ratio(late0, p_late, r_late),
        "frozen_leaves": float(
            frozen_leaves(p0, prog["params"][-1], ref["params"][-1])
            + frozen_leaves(late0, p_late, r_late)),
        "param_dtype_mismatch": float(prog.get("dtype_mismatch", 0)),
        "params_bf16_grid_gap": params_grid_share(p_late)
        - params_grid_share(r_late),
        "update_bf16_grid_gap": update_grid_share(late0, p_late)
        - update_grid_share(late0, r_late),
        # the contract's per-leaf numbers and the relative L2, chaotic
        # in this cell (PERF.md section 2)
        "grad_norm_gap": worst_leaf_norm_gap(p0, prog["params"][0],
                                             ref["params"][0]),
        "update_late_rel_l2": rel_l2(late0, p_late, r_late),
        "update_r0_rel_l2": rel_l2(p0, prog["params"][0], ref["params"][0]),
        "change_worst_leaf_gap": worst_leaf_norm_gap(
            p0, prog["params"][-1], ref["params"][-1]),
        "late_worst_leaf_gap": worst_leaf_norm_gap(late0, p_late, r_late),
        "loss_r012_rel": max(rel_gap(a, b) for a, b in
                             zip(prog["losses"], ref["losses"])),
    }


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each judged number (a key of ``limits``) beside its limit."""
    lines, ok = [], True
    for name, limit in limits.items():
        good = bool(np.isfinite(numbers[name]) and numbers[name] <= limit)
        ok = ok and good
        lines.append(f"{name} = {numbers[name]:.6g} (limit {limit:g})"
                     f" {'ok' if good else 'FAILED'}")
    lines.append("not judged: " + ", ".join(
        f"{k} = {v:.4g}" for k, v in numbers.items() if k not in limits))
    return {"correct": ok, "lines": lines, "numbers": numbers}


# -- the two sides ---------------------------------------------------------

def gather_case(cfg, trainer, late: dict, rounds: int = 3) -> dict:
    """The reference's inputs: initial parameters, each seeded round's
    cohort and batches, and the late round's with the parameters (and
    client state) it started from."""
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


def run_reference(case: dict, arch: str, algorithm: str, hp: dict,
                  cast=None, param_cast=None, accum_cast=None) -> dict:
    """Follow the case with the plain reference. ``cast``,
    ``param_cast`` and ``accum_cast`` are the control's hooks (operands
    of every product; parameters after every update; the server's
    running sum of the clients' movements); identity for the
    reference."""
    import jax
    import jax.numpy as jnp

    from . import runner
    from ..reference import _ops

    model = runner.load_by_name("reference", arch)
    alg = runner.load_by_name("reference", algorithm)
    cast = cast or _ops.identity
    param_cast = param_cast or _ops.identity
    accum_cast = accum_cast or _ops.identity
    f32 = lambda t: jax.tree.map(
        lambda x: param_cast(jnp.asarray(x, jnp.float32)), t)
    with jax.default_matmul_precision("highest"):
        run_round = alg.make_round(model.loss, hp, cast, param_cast,
                                   accum_cast)
        server, state = f32(case["p0"]), None
        params, losses = [], []
        for cohort, xs, ys in case["rounds"]:
            server, state, loss = run_round(
                server, state, cohort, jnp.asarray(xs), jnp.asarray(ys))
            params.append(jax.device_get(server))
            losses.append(float(loss))
        cohort, xs, ys = case["late"]["round"]
        late, _, late_loss = run_round(
            f32(case["late"]["p"]), case["late"]["state"], cohort,
            jnp.asarray(xs), jnp.asarray(ys))
    return {"params": params, "losses": losses,
            "late_params": jax.device_get(late),
            "late_loss": float(late_loss)}


def dtype_mismatch(trees, stated: str) -> int:
    """Floating leaves of the live state whose type is not ``stated``."""
    import jax
    import jax.numpy as jnp

    return sum(1 for x in jax.tree.leaves(trees)
               if jnp.issubdtype(x.dtype, jnp.floating)
               and x.dtype != jnp.dtype(stated))


def check(cell: dict, cfg, trainer, params_after: dict, late: dict,
          rows: List[dict]) -> dict:
    """The run's verdict. ``params_after``: {round: host parameters} of
    rounds 0-2; ``late``: {"round", "before", "after", "state"} of the
    round after the window; both as the loop's callback copied them."""
    cfgf, tf = cell["config_file"], cell["traffic_file"]
    n = len(params_after)
    case = gather_case(cfg, trainer, late, n)
    by_round = {r["round"]: r for r in rows}
    prog = {"params": [params_after[r] for r in range(n)],
            "losses": [by_round[r]["loss"] for r in range(n)],
            "late_params": late["after"],
            "late_loss": by_round[late["round"]]["loss"],
            "dtype_mismatch": late["dtype_mismatch"]}
    hp = hyper(cfg)
    ref = run_reference(case, cfgf["arch"], tf["algorithm"], hp)
    out = verdict(compare(case, prog, ref), cfgf["correct"]["limits"])
    out["lines"].insert(0, f"losses program {prog['losses']} late "
                        f"{prog['late_loss']}; reference {ref['losses']} "
                        f"late {ref['late_loss']}")
    out["case"], out["prog"], out["ref"], out["hp"] = case, prog, ref, hp
    return out
