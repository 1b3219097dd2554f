"""``correct`` on the CPU at a size a test run can hold, all through
the one cell's own files and against its own limits: a sound run, the
controls put in the program's place, and runs with the timed path
broken underneath. Beside them the plain reference against the program
in float32, where the two agree far more closely than the bfloat16
cell's limits ask.
"""
import json

import numpy as np
import pytest

from benchmark.harness import correct, runner
from benchmark.reference import _ops

CELL = "resnet20_c100.fedavg_k10"
BATCH = 8
# sizes a CPU test run can hold: same code path, nothing measured
TINY = {"datagen": {"train_images": 2000, "test_images": 200},
        "launcher": {"num_workers": 8, "batch_size": BATCH,
                     "local_step": 2}}

# What two float32 implementations of the same rounds agree to on
# ResNet-20: float32 rounding amplified by its nineteen batch-statistics
# normalizations (at batch 8 a single float32 gradient, the program's or
# the reference's, is already 2e-3 to 3e-3 from its float64 twin) and
# again by every further step at lr 0.1. Read at batch 32, 2 steps, 2
# clients, loss_r0_rel / change_norm_gap / loss_late_rel: sound 5e-6 /
# 1.4e-3 / 5e-7; bfloat16 parameters 1.9e-4 / 0.012 / 2.7e-4; bfloat16
# operands 2.1e-4 / 1.2e-3 / 2.6e-4.
F32_LIMITS = {"loss_r0_rel": 5e-5, "change_norm_gap": 0.006,
              "loss_late_rel": 5e-5}
CONTROLS = {"fp8": dict(cast=_ops.fp8_round_trip),
            "bf16_params": dict(param_cast=_ops.bf16_round_trip),
            "bf16_accum": dict(accum_cast=_ops.bf16_round_trip),
            "bf16_compute": dict(cast=_ops.bf16_round_trip)}


def tiny(dtype, **launcher):
    ov = {k: dict(v) for k, v in TINY.items()}
    ov["launcher"].update(compute_dtype=dtype, **launcher)
    return ov


def limits():
    return runner.load_cell(CELL)["config_file"]["correct"]["limits"]


def control(chk, name):
    c = runner.load_cell(CELL)
    model = runner.load_by_name("reference", c["config_file"]["arch"])
    low = correct.as_program(correct.reference_rounds(
        chk["case"], model.loss, c["traffic_file"]["algorithm"], chk["hp"],
        **CONTROLS[name]))
    return correct.compare(chk["case"], low, chk["ref"])


@pytest.fixture(scope="module")
def sound_run():
    """One sound bfloat16 run at the tiny size, its case and both sides
    kept for the controls."""
    return runner.run_cell(CELL, 9, 1.0, False, require_chip=False,
                           overrides=tiny("bfloat16"), keep_check=True)


@pytest.fixture(scope="module")
def f32_run():
    ov = tiny("float32", batch_size=32)
    ov["datagen"]["train_images"] = 4000
    ov["traffic"] = {"launcher": dict(
        runner.load_cell(CELL)["traffic_file"]["launcher"],
        online_client_rate=0.25)}
    return runner.run_cell(CELL, 5, 1.0, False, require_chip=False,
                           overrides=ov, keep_check=True)


def test_sound_run_is_correct_under_the_cells_own_limits(sound_run):
    res = sound_run
    assert res["correct"] is True, res["_check"]["lines"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"round_s_p50", "peak_hbm_gib",
                                   "setup_s"}
    assert np.isfinite(res["metrics"]["round_s_p50"]["value"])
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    # every judged number is printed beside its limit
    lines = "\n".join(res["_check"]["lines"])
    for name, limit in limits().items():
        assert f"{name} = " in lines and f"(limit {limit:g})" in lines


def test_a_run_that_keeps_nothing_judges_alike(sound_run, capsys):
    """As the benchmark's own runs go: the reference's rounds run as
    the comparison asks for them and the trees are let go of as read.
    Every line of the verdict is the kept run's."""
    res = runner.run_cell(CELL, 9, 1.0, False, require_chip=False,
                          overrides=tiny("bfloat16"))
    assert res["correct"] is True and "_check" not in res
    said = capsys.readouterr()
    printed = [line.split("benchmark: correct: ", 1)[1]
               for line in said.out.splitlines()
               if line.startswith("benchmark: correct: ")]
    assert printed == sound_run["_check"]["lines"]
    # each number compared beside its limit: the result's last key and
    # the last lines on standard error
    assert list(res)[-1] == "compared" and json.loads(json.dumps(res))
    assert {k: c["limit"] for k, c in res["compared"].items()} == limits()
    assert said.err.splitlines()[-len(limits()):] == [
        f"benchmark: compared {k} = {c['value']} limit {c['limit']}"
        for k, c in res["compared"].items()]


@pytest.mark.parametrize("name,number", [
    ("fp8", None),
    ("bf16_params", "params_bf16_grid_gap"),
    ("bf16_accum", "update_bf16_grid_gap")])
def test_control_fails_the_cells_own_limits(sound_run, name, number):
    """The reference computed below the stated precision, in the
    program's place, comes out as not correct: float8 operands where
    bfloat16 is stated, bfloat16 parameters and bfloat16 accumulation
    where float32 is stated."""
    numbers = control(sound_run["_check"], name)
    v = correct.verdict(numbers, limits())
    assert not v["correct"], v["lines"]
    if number:
        assert numbers[number] > limits()[number], v["lines"]


def test_reference_agrees_with_the_program_in_float32(f32_run):
    chk = f32_run["_check"]
    # round 0's loss is the forward pass at the seeded weights
    assert chk["prog"]["losses"][0] == pytest.approx(
        chk["ref"][0][1], rel=1e-4)
    for name, limit in F32_LIMITS.items():
        assert chk["numbers"][name] < limit, (name, chk["numbers"])
    assert chk["numbers"]["frozen_leaves"] == 0
    assert chk["numbers"]["param_dtype_mismatch"] == 0


@pytest.mark.parametrize("name", ["bf16_params", "bf16_compute"])
def test_float32_limit_rejects_lower_precision(f32_run, name):
    """The negative case: the reference with its parameters (or its
    operands) cast to bfloat16 is outside what two float32 sides
    agree to."""
    chk = f32_run["_check"]
    numbers = control(chk, name)
    failed = [n for n in F32_LIMITS if numbers[n] > F32_LIMITS[n]
              and numbers[n] > 3 * chk["numbers"][n]]
    assert failed, (numbers, chk["numbers"])


def _break(monkeypatch, how):
    """Break the timed path underneath the harness; returns the judged
    number that has to catch it."""
    import jax
    import jax.numpy as jnp

    from fedtorch_tpu.parallel import FederatedTrainer, federated
    real = FederatedTrainer.run_round
    calls = {"n": 0}

    if how == "half_batch":
        # a part of the batch left out: the second half of every batch
        # the program takes repeats the first (the harness rebuilds the
        # reference's batches from the unpatched helper)
        plan = federated.round_row_plan

        def half(*a, **kw):
            rows = plan(*a, **kw).reshape((-1, BATCH))
            return jnp.concatenate([rows[:, :BATCH // 2]] * 2,
                                   axis=1).reshape((-1,))
        monkeypatch.setattr(federated, "round_row_plan", half)
        return "loss_r0_rel"

    def broken(self, server, clients):
        calls["n"] += 1
        # the buffers are donated, so keep a copy to hand back
        kept = jax.tree.map(lambda x: x.copy(), server.params)
        server, clients, metrics = real(self, server, clients)
        leaves, tree = jax.tree.flatten(server.params)
        if how == "state_unchanged":
            # a step that returns its state unchanged, every time
            leaves = jax.tree.leaves(kept)
        elif how == "leaf_frozen":
            # one leaf not updated: the smallest, every time
            i = min(range(len(leaves)), key=lambda j: leaves[j].size)
            leaves[i] = jax.tree.leaves(kept)[i]
        elif how == "update_scaled" and calls["n"] == 1:
            # an answer altered where it is produced: the largest leaf
            # of the first round's parameters tripled
            i = max(range(len(leaves)), key=lambda j: leaves[j].size)
            leaves[i] = leaves[i] * 3.0
        elif how == "params_bf16":
            # parameters held below the stated float32
            leaves = [_ops.bf16_round_trip(x) for x in leaves]
        server = server._replace(params=jax.tree.unflatten(tree, leaves))
        return server, clients, metrics

    monkeypatch.setattr(FederatedTrainer, "run_round", broken)
    return {"state_unchanged": "change_norm_gap",
            "leaf_frozen": "frozen_leaves",
            "update_scaled": "change_norm_gap",
            "params_bf16": "params_bf16_grid_gap"}[how]


@pytest.mark.parametrize("how", ["state_unchanged", "half_batch",
                                 "leaf_frozen", "update_scaled",
                                 "params_bf16"])
def test_broken_timed_path_comes_out_not_correct(monkeypatch, how):
    number = _break(monkeypatch, how)
    res = runner.run_cell(CELL, 9, 1.0, False, require_chip=False,
                          overrides=tiny("bfloat16"), keep_check=True)
    assert res["correct"] is False
    assert res["_check"]["numbers"][number] > limits()[number], \
        res["_check"]["lines"]
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device"}


def test_no_chip_no_result(capsys):
    """The command's look for a chip: on the CPU it exits non-zero
    before anything compiles and prints no result."""
    with pytest.raises(SystemExit) as e:
        runner.run_cell(CELL, 1, 1.0, False)
    assert e.value.code not in (0, None)
    assert '"correct"' not in capsys.readouterr().out
