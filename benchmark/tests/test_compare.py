"""``correct.compare``'s one walk over the leaves against the
whole-tree arithmetic it replaced, which is written out here as the
oracle: every tree converted to float64 whole, one helper a number.
Seeded trees with the cases a run can meet; every one of the sixteen
numbers equal to 1e-12 relative.
"""
import math
import statistics

import numpy as np
import pytest

from benchmark.harness import correct


# -- the oracle: benchmark/harness/correct.py as PR 23 wrote it ------------

def _leaves(tree):
    import jax

    return [np.asarray(x, np.float64) for x in jax.tree.leaves(tree)]


def leaf_norms(p0, p1):
    return [float(np.linalg.norm(a - z))
            for a, z in zip(_leaves(p1), _leaves(p0))]


def worst_leaf_norm_gap(p0, prog, ref):
    pn, rn = leaf_norms(p0, prog), leaf_norms(p0, ref)
    med = statistics.median(rn)
    return max(abs(p - r) / max(r, med, 1e-30) for p, r in zip(pn, rn))


def worst_leaf_ratio(p0, prog, ref):
    worst = 0.0
    for p, r in zip(leaf_norms(p0, prog), leaf_norms(p0, ref)):
        if r > 0.0:
            worst = max(worst, abs(math.log(p / r)) if p > 0.0 else 99.0)
    return worst


def frozen_leaves(p0, prog, ref):
    return sum(1 for p, r in zip(leaf_norms(p0, prog), leaf_norms(p0, ref))
               if p == 0.0 and r > 0.0)


def global_norm_gap(p0, prog, ref):
    pn = sum(v * v for v in leaf_norms(p0, prog)) ** 0.5
    rn = sum(v * v for v in leaf_norms(p0, ref)) ** 0.5
    return abs(pn - rn) / max(rn, 1e-30)


def rel_l2(p0, prog, ref):
    num = den = 0.0
    for a, b, z in zip(_leaves(prog), _leaves(ref), _leaves(p0)):
        num += float(np.sum(np.square(a - b)))
        den += float(np.sum(np.square(b - z)))
    return (num / max(den, 1e-60)) ** 0.5


def cos_gap(p0, prog, ref):
    dot = pp = rr = 0.0
    for a, b, z in zip(_leaves(prog), _leaves(ref), _leaves(p0)):
        dot += float(np.sum((a - z) * (b - z)))
        pp += float(np.sum(np.square(a - z)))
        rr += float(np.sum(np.square(b - z)))
    return 1.0 - dot / max((pp * rr) ** 0.5, 1e-60)


def bf16_grid_share(values):
    on = n = 0
    for v in values:
        low = np.ascontiguousarray(v, np.float32).view(np.uint32) & 0xFFFF
        on += int(np.count_nonzero(np.minimum(low, 65536 - low) < 2048))
        n += low.size
    return on / n if n else 0.0


def params_grid_share(params):
    return bf16_grid_share([x[(x != 0.0) & np.isfinite(x)]
                            for x in _leaves(params)])


def update_grid_share(p0, p1):
    picked = []
    for a, z in zip(_leaves(p1), _leaves(p0)):
        u = a - z
        picked.append(u[(np.abs(u) >= np.abs(a) * 2.0 ** -10)
                        & (u != 0.0)])
    return bf16_grid_share(picked)


def oracle(case, prog, ref):
    rel_gap = correct.rel_gap
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


# -- seeded trees -----------------------------------------------------------

SHAPES = {"conv": (3, 3, 16, 32), "scale": (32,), "dense": (64, 10),
          "bias": (10,), "wide": (700, 400)}    # wide: more than a block


def bf16(x):
    import ml_dtypes

    return x.astype(ml_dtypes.bfloat16).astype(np.float32)


def seeded(seed, how):
    """A case and its two sides: every tree the start plus a seeded
    movement, the program's 10 % off the reference's; ``how`` plants
    the one thing the case is about."""
    rng = np.random.default_rng(seed)

    def tree(scale=1.0, base=None):
        out = {}
        for k, s in SHAPES.items():
            v = (scale * rng.standard_normal(s)).astype(np.float32)
            out[k] = v if base is None else (base[k] + v).astype(np.float32)
        return out

    def moved(start, step):
        ref = tree(step, start)
        prog = {k: (ref[k] + 0.1 * step * rng.standard_normal(
            ref[k].shape)).astype(np.float32) for k in ref}
        return prog, ref

    p0, late0 = tree(), tree()
    (p_r0, r_r0), (p_r2, r_r2) = moved(p0, 0.01), moved(p0, 0.03)
    p_late, r_late = moved(late0, 0.01)
    if how == "program_froze_a_leaf":
        p_r2["scale"] = p0["scale"].copy()
        p_late["bias"] = late0["bias"].copy()
    elif how == "reference_did_not_move_a_leaf":
        r_r2["bias"] = p0["bias"].copy()
        r_late["scale"] = late0["scale"].copy()
    elif how == "leaf_of_zeros":
        for t in (p0, p_r0, r_r0, p_r2, r_r2, late0, p_late, r_late):
            t["bias"] = np.zeros_like(t["bias"])
    elif how == "params_rounded_to_bf16":
        p_late = {k: bf16(v) for k, v in p_late.items()}
    elif how == "update_rounded_to_bf16":
        p_late = {k: (late0[k] + bf16(v - late0[k])).astype(np.float32)
                  for k, v in p_late.items()}
    elif how == "program_norm_of_zero":
        p_r0, p_r2 = p0, p0
        p_late = late0
    elif how == "not_finite":
        p_late["dense"][0, 0] = np.inf
        r_late["conv"][0, 0, 0, 0] = np.nan
    elif how == "half_precision_leaves":
        p_late = {k: v.astype(np.float16) for k, v in p_late.items()}
    else:
        assert how == "plain", how
    case = {"p0": p0, "late": {"p": late0}}
    prog = {"params": [p_r0, tree(), p_r2], "losses": [2.31, 2.2, 2.05],
            "late_params": p_late, "late_loss": 0.52, "dtype_mismatch": 2}
    ref = {"params": [r_r0, tree(), r_r2], "losses": [2.30, 2.25, 2.0],
           "late_params": r_late, "late_loss": 0.5}
    return case, prog, ref


def rounds(ref):
    """The reference's side as ``compare`` takes it: its rounds in
    order, the seeded ones and then the late one."""
    return list(zip(ref["params"], ref["losses"])) + [
        (ref["late_params"], ref["late_loss"])]


CASES = ["plain", "program_froze_a_leaf", "reference_did_not_move_a_leaf",
         "leaf_of_zeros", "params_rounded_to_bf16", "update_rounded_to_bf16",
         "program_norm_of_zero", "not_finite", "half_precision_leaves"]


@pytest.mark.parametrize("seed", [1, 2])
@pytest.mark.parametrize("how", CASES)
def test_one_walk_gives_the_whole_tree_numbers(how, seed):
    case, prog, ref = seeded(seed, how)
    assert max(v.size for v in case["p0"].values()) > correct.BLOCK
    with np.errstate(invalid="ignore"):
        want = oracle(case, prog, ref)
        got = correct.compare(case, prog, rounds(ref))
    assert list(got) == list(want) and len(got) == 16
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-12, abs=0,
                                          nan_ok=True), name
    # the planted thing is seen, so the case is about something
    seen = {"program_froze_a_leaf": got["frozen_leaves"] == 2,
            "program_norm_of_zero": got["change_norm_gap"] == 1.0
            and got["frozen_leaves"] == 2 * len(SHAPES),
            "params_rounded_to_bf16": got["params_bf16_grid_gap"] > 0.9,
            "update_rounded_to_bf16": got["update_bf16_grid_gap"] > 0.9,
            "leaf_of_zeros": got["frozen_leaves"] == 0}
    assert seen.get(how, True), got


def test_one_round_followed_reads_the_same_tree_twice():
    """Where one seeded round is followed, the first and the last tree
    are the same object."""
    case, prog, ref = seeded(3, "plain")
    for side in (prog, ref):
        side["params"], side["losses"] = side["params"][:1], \
            side["losses"][:1]
    want = oracle(case, prog, ref)
    got = correct.compare(case, prog, rounds(ref))
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-12, abs=0)


def test_no_whole_tree_in_float64(monkeypatch):
    """Nothing beyond a block's scratch is made: at a block of 4096
    elements the walk's peak is the scratch, far under one leaf in
    float64, where the whole-tree arithmetic holds several trees."""
    import tracemalloc

    monkeypatch.setattr(correct, "BLOCK", 4096)
    case, prog, ref = seeded(4, "plain")
    scratch = 4096 * (5 * 8 + 4 + 4 + 2)
    leaf64 = 8 * max(v.size for v in case["p0"].values())
    peaks = []
    for fn, side in ((correct.compare, rounds(ref)), (oracle, ref)):
        tracemalloc.start()
        fn(case, prog, side)
        peaks.append(tracemalloc.get_traced_memory()[1])
        tracemalloc.stop()
    assert peaks[0] < 1.5 * scratch < leaf64 < peaks[1]


def test_trees_are_let_go_of_as_the_rounds_come():
    """As ``check`` runs it: the reference's rounds from a generator,
    ``release`` on. The numbers are those of the whole lists; no round
    is asked for before the one before it is compared; and the seeded
    stretch's trees are gone from ``case`` and ``prog`` before the late
    round is asked for, the first round's before the second."""
    case, prog, ref = seeded(5, "plain")
    want = oracle(case, prog, ref)
    held = []

    def lazily():
        for i, item in enumerate(rounds(ref)):
            held.append((i, "p0" in case,
                         [t is not None for t in prog["params"]]))
            yield item

    got = correct.compare(case, prog, lazily(), release=True)
    for name in want:
        assert got[name] == pytest.approx(want[name], rel=1e-12, abs=0)
    assert held == [(0, True, [True, True, True]),
                    (1, True, [False, True, True]),
                    (2, True, [False, True, True]),
                    (3, False, [])]
    assert "late" in case and "late_params" in prog
