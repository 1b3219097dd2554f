"""The looped language model (``models/hybrid_lm.py`` with
``total_ut_steps`` > 1: the Ouro block, rotary embedding, an exit gate
and a head after every pass) against the plain reference
(``benchmark/reference/ouro.py``) at a small specification; the
objective's own properties; what the engine does with it (the
sequential round against the vmapped one, what the compiled round
holds, the launcher through rounds, evaluation, save and resume); the
specification's loader; and that the single-pass model's round program
is the one it was."""
import hashlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.datagen import tokens as token_files
from benchmark.reference import ouro as reference
from fedtorch_tpu.models import hybrid_lm
from fedtorch_tpu.models.hybrid_lm import (
    HybridLM, exit_objective, load_spec, param_shapes, rotary_tables,
)
from test_sequential_round import (
    gauges_of, lm_cfg, round_rows, trainer_of,
)

SMALL = {
    "model_type": "ouro", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 48, "num_hidden_layers": 2,
    # more entries than num_hidden_layers: the list is cut to it
    "layer_types": ["full_attention"] * 4,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000.0, "rope_scaling": None,
    "total_ut_steps": 3, "exit_entropy_beta": 0.05,
    "launcher": {"ignored": True},
}
CLIENTS = 6


def write_spec(tmp_path, name="spec.json", **change):
    path = tmp_path / name
    doc = {k: v for k, v in dict(SMALL, **change).items()
           if v is not ...}
    path.write_text(json.dumps(doc))
    return str(path)


@pytest.fixture
def spec_file(tmp_path):
    return write_spec(tmp_path)


def model_of(spec_file, **kw):
    kw = dict(dict(dtype="float32", attention="auto", remat=True), **kw)
    return HybridLM("hybrid_lm", load_spec(spec_file), **kw)


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, SMALL["vocab_size"], shape), jnp.int32)


def open_gate(params):
    """Seeded parameters with a gate that is not near its symmetric
    start, so that every exit holds a different mass."""
    gate = {"w": params["exit_gate"]["w"] * 20.0,
            "b": jnp.asarray([0.3], jnp.float32)}
    return dict(params, exit_gate=gate)


def loss_and_grads(model, params, x):
    def f(p):
        loss, acc, parts = model.token_loss_parts(p, x)
        return loss, (acc, parts)
    return jax.jit(jax.value_and_grad(f, has_aux=True))(params)


def worst_gap(got, want):
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)), got, want)
    return max(jax.tree.leaves(gaps)), gaps


# -- against the reference --------------------------------------------------

@pytest.mark.parametrize("remat", [True, False])
def test_objective_and_gradients_equal_the_reference(spec_file, remat):
    model = model_of(spec_file, remat=remat)
    params = open_gate(model.init(jax.random.key(1)))
    x = tokens((2, 20))
    spec = reference.load_spec(spec_file)
    with jax.default_matmul_precision("highest"):
        (loss, (acc, parts)), grads = loss_and_grads(model, params, x)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.make_loss(spec)(p, x, None)))(params)
        _, ces, masses = reference.objective(params, x, spec)
    assert 0.0 <= float(acc) <= 1.0
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(parts["exit_ce"], ces, rtol=1e-6)
    np.testing.assert_allclose(parts["exit_mass"], masses, rtol=1e-5)
    worst, gaps = worst_gap(grads, want_grads)
    assert worst < 1e-5, gaps
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))


def test_bfloat16_operands_stay_inside_a_band(spec_file):
    """bfloat16 carries 8 bits: a product's operands are right to
    2**-9, and three passes of two layers compound it. At this size
    the objective reads within 1e-3 of float32's and every leaf's
    gradient within 6 % of its largest element (measured 2.3e-5 and
    3.7 %); float32 parameters throughout."""
    model = model_of(spec_file, dtype="bfloat16")
    params = open_gate(model.init(jax.random.key(1)))
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype("float32")}
    x = tokens((2, 20))
    (loss16, _), g16 = loss_and_grads(model, params, x)
    (loss32, _), g32 = loss_and_grads(model_of(spec_file), params, x)
    assert loss16.dtype == jnp.float32
    assert {g.dtype for g in jax.tree.leaves(g16)} == {
        jnp.dtype("float32")}
    assert abs(float(loss16) - float(loss32)) < 1e-3 * float(loss32)
    worst, gaps = worst_gap(g16, g32)
    assert 1e-4 < worst < 0.06, gaps


def test_evaluation_reads_the_last_pass(spec_file):
    from fedtorch_tpu.parallel.evaluate import evaluate
    model = model_of(spec_file)
    params = open_gate(model.init(jax.random.key(1)))
    rows = np.asarray(tokens((3, 20), seed=4))
    res = jax.device_get(evaluate(model, params, rows,
                                  np.zeros(3, np.int32)))
    last_ce = jax.jit(lambda row: model.token_loss_parts(
        params, row)[2]["exit_ce"][-1])
    want = np.mean([float(last_ce(rows[i:i + 1])) for i in range(3)])
    np.testing.assert_allclose(res.loss, want, rtol=1e-5)
    logits = jax.jit(model.apply)(params, rows[:1])
    assert logits.shape == (1, 20, SMALL["vocab_size"])
    assert 0.0 <= float(res.top1) <= float(res.top5) <= 1.0


# -- the objective ------------------------------------------------------------

def test_one_pass_without_entropy_is_the_plain_loss(tmp_path):
    """``R = 1``: the exit distribution is one point, the objective the
    next-token cross-entropy; the model then has no gate to hold."""
    nll = jnp.asarray(np.random.RandomState(0).rand(1, 2, 7), jnp.float32)
    z = jnp.asarray(np.random.RandomState(1).randn(1, 2, 7), jnp.float32)
    loss, parts = exit_objective(nll, z, 0.0)
    np.testing.assert_allclose(loss, jnp.mean(nll), rtol=1e-6)
    np.testing.assert_allclose(parts["exit_mass"], [1.0])
    assert float(parts["exit_entropy"]) == 0.0
    model = model_of(write_spec(tmp_path, total_ut_steps=1,
                                exit_entropy_beta=0.0))
    params = model.init(jax.random.key(2))
    assert "exit_gate" not in params
    x = tokens((1, 24), seed=3)
    loss, _, parts = jax.jit(model.token_loss_parts)(params, x)
    assert parts == {}
    logp = jax.nn.log_softmax(jax.jit(model.apply)(params, x)[:, :-1])
    want = -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))
    np.testing.assert_allclose(loss, want, rtol=1e-6)


def test_exit_masses_sum_to_one_and_entropy_reaches_the_gate(tmp_path):
    x = tokens((2, 20))
    grads = {}
    for beta in (0.0, 0.05):
        model = model_of(write_spec(tmp_path, exit_entropy_beta=beta))
        params = open_gate(model.init(jax.random.key(1)))
        (_, (_, parts)), g = loss_and_grads(model, params, x)
        np.testing.assert_allclose(jnp.sum(parts["exit_mass"]), 1.0,
                                   rtol=1e-6)
        assert 0.0 < float(parts["exit_entropy"]) < np.log(3.0)
        grads[beta] = g["exit_gate"]
    # the entropy term's own gradient: what beta adds to the gate's
    for leaf in ("w", "b"):
        added = grads[0.05][leaf] - grads[0.0][leaf]
        assert float(jnp.max(jnp.abs(added))) > 1e-5
    # and by hand at one position: d/dz of -beta H(q) is not zero
    z = jnp.asarray([[[0.4]], [[-0.2]], [[0.0]]], jnp.float32)
    d = jax.grad(lambda z: exit_objective(jnp.zeros_like(z), z, 1.0)[0])(z)
    assert float(jnp.abs(d[0, 0, 0])) > 1e-3 and float(d[2, 0, 0]) == 0.0


def test_looped_gradient_is_the_sum_over_untied_passes(spec_file):
    """Give every pass its own copy of the layers and of the final
    norm: the looped model's gradient of a layer is the sum of the
    copies' gradients."""
    model = model_of(spec_file)
    s, dt = model.spec, jnp.float32
    params = open_gate(model.init(jax.random.key(1)))
    x = tokens((2, 20))
    looped = [k for k in params if k.startswith("layer_")] + ["final_norm"]

    def untied(copies, shared):
        h, hs = shared["embed"][x], []
        rope = hybrid_lm._rope(s, x.shape[1])
        for own in copies:
            h = hybrid_lm._rms_norm(
                hybrid_lm._stack(own, h, s, dt, "auto", True, rope),
                own["final_norm"], s.rms_norm_eps)
            hs.append(h)
        hs = jnp.stack(hs)
        nll, _ = hybrid_lm.exit_stats(shared, hs, x, dt)
        return exit_objective(
            nll, hybrid_lm.exit_gate(shared, hs)[..., :-1],
            s.exit_entropy_beta)[0]

    copies = [{k: params[k] for k in looped}] * s.total_ut_steps
    shared = {k: v for k, v in params.items() if k not in looped}
    loss, (g_copies, g_shared) = jax.jit(jax.value_and_grad(
        untied, argnums=(0, 1)))(copies, shared)
    (want_loss, _), want = loss_and_grads(model, params, x)
    np.testing.assert_allclose(loss, want_loss, rtol=1e-6)
    summed = jax.tree.map(lambda *g: sum(g), *g_copies)
    worst, gaps = worst_gap(dict(summed, **g_shared), want)
    assert worst < 1e-5, gaps
    # the passes do differ: no copy alone carries the gradient
    alone, _ = worst_gap(g_copies[0], {k: want[k] for k in looped})
    assert alone > 0.1


def test_rotary_attention_is_invariant_to_a_shift_of_positions(spec_file):
    s = load_spec(spec_file)
    p = model_of(spec_file).init(jax.random.key(3))["layer_0"]["mixer"]
    u = jnp.asarray(np.random.RandomState(0).randn(2, 12, 32), jnp.float32)
    hd = s.hidden_size // s.num_attention_heads

    def attend(offset):
        rope = rotary_tables(jnp.arange(12) + offset, hd, s.rope_theta)
        return hybrid_lm._full_attention(p, u, s, jnp.float32, "dense",
                                         rope)

    with jax.default_matmul_precision("highest"):
        at0, at977, bare = attend(0), attend(977), \
            hybrid_lm._full_attention(p, u, s, jnp.float32, "dense")
    np.testing.assert_allclose(at0, at977, rtol=1e-3, atol=1e-6)
    # and the embedding does something: without it the output differs
    assert float(jnp.max(jnp.abs(at0 - bare))) > 1e-4


# -- the specification --------------------------------------------------------

def test_the_benchmarks_configuration_counts_its_parameters():
    """The published widths with the cut the file states: counted from
    shapes, nothing allocated."""
    path = "benchmark/configs/ouro_2_6b_l8.json"
    spec = load_spec(path)
    with open(path) as f:
        doc = json.load(f)
    assert spec.layer_types == ("full_attention",) * 8
    assert len(doc["layer_types"]) == doc["published"]["num_hidden_layers"]
    assert (spec.total_ut_steps, spec.rope_theta, spec.sandwich) == (
        4, 1e6, True)
    shapes = param_shapes(spec)
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(
        t, is_leaf=lambda s: isinstance(s, tuple)))
    assert count(shapes["layer_0"]) == doc["parameters"]["layer"] \
        == 51388416
    assert count(shapes["exit_gate"]) == doc["parameters"]["exit_gate"]
    assert count(shapes) == doc["parameters"]["total"] == 612438017


def test_the_single_pass_files_read_as_they_did(tmp_path):
    """The Olmo file: no new leaf, the defaults of the new fields."""
    spec = load_spec("benchmark/configs/olmo_hybrid_7b_l4.json")
    assert (spec.total_ut_steps, spec.rope_theta, spec.sandwich) == (
        1, None, False)
    assert set(param_shapes(spec)) == {
        "embed", "final_norm", "head", "layer_0", "layer_1", "layer_2",
        "layer_3"}
    assert set(param_shapes(spec)["layer_3"]["mixer"]) == {
        "wq", "wk", "wv", "wo", "q_norm", "k_norm"}
    # rope_theta under rope_parameters, as the Olmo family writes it
    nested = load_spec(write_spec(
        tmp_path, rope_theta=..., rope_parameters={"rope_theta": 5e5}))
    assert nested.rope_theta == 5e5


@pytest.mark.parametrize("change,mixer", [
    ({"head_dim": 16}, {"wq": (32, 64), "wk": (32, 64), "wv": (32, 64),
                        "wo": (64, 32)}),
    ({"num_key_value_heads": 2}, {"wq": (32, 32), "wk": (32, 16),
                                  "wv": (32, 16), "wo": (32, 32)}),
])
def test_a_looped_file_with_grouped_or_wider_heads_is_read(tmp_path, change,
                                                            mixer):
    """Refused until PR 39; the sandwich block has no QK-norm, so the
    mixer is the four projections at the stated head counts and size,
    and the looped objective trains through them."""
    model = model_of(write_spec(tmp_path, **change))
    assert param_shapes(model.spec)["layer_0"]["mixer"] == mixer
    params = open_gate(model.init(jax.random.key(2)))
    (loss, _), grads = loss_and_grads(model, params, tokens((1, 12)))
    assert np.isfinite(float(loss))
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(grads["layer_0"]["mixer"]))


@pytest.mark.parametrize("change,match", [
    ({"model_type": "olmo_hybrid"}, r"total_ut_steps 3 with model_type "
                                    r"'olmo_hybrid'"),
    ({"model_type": ...}, "total_ut_steps 3 with model_type None"),
    ({"total_ut_steps": 0}, "total_ut_steps 0"),
    ({"head_dim": 7}, "head_dim 7 is odd"),
    ({"rope_scaling": {"factor": 2.0}}, "rope_scaling"),
    ({"layer_types": ["full_attention", "linear_attention"]},
     "lacks.*linear_num_key_heads"),
    ({"num_key_value_heads": 3}, "no multiple of num_key_value_heads"),
    ({"intermediate_size": ...}, "lacks.*intermediate_size"),
])
def test_specification_refusals_by_name(tmp_path, change, match):
    with pytest.raises(ValueError, match=match):
        load_spec(write_spec(tmp_path, "bad.json", **change))


# -- in the engine --------------------------------------------------------------

@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("looped")
    data = token_files.write(str(root / "data"), 7, clients=CLIENTS,
                             rows_per_client=5, seq_len=24,
                             vocab_size=SMALL["vocab_size"], test_rows=3)
    return {"spec": write_spec(root), "data": data}


def test_sequential_round_equals_the_vmapped_round(files):
    """Same cohort, rows, keys and weights for the looped model: the
    server's parameters agree to float32 rounding of the sum's order;
    the sequential round also reports the exits' gauges."""
    out = {}
    for execution in ("vmap", "sequential"):
        t = trainer_of(lm_cfg(files, execution))
        server, clients = t.init_state(jax.random.key(3))
        losses = []
        for _ in range(2):
            server, clients, m = t.run_round(server, clients)
            losses.append(np.asarray(m.train_loss))
        out[execution] = (jax.device_get(server.params), losses, m, t)
    (pv, lv, mv, _), (ps, ls, ms, ts) = out["vmap"], out["sequential"]
    np.testing.assert_allclose(lv, ls, rtol=1e-5)
    for a, b in zip(jax.tree.leaves(pv), jax.tree.leaves(ps)):
        np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-7)
    assert mv.model_gauges is None
    g = gauges_of(ts, ms)
    assert 0.0 < float(g["lm_exit_mass_last"]) < 1.0
    assert 0.0 < float(g["lm_exit_entropy"]) < np.log(3.0)
    gauges = ts.telemetry_gauges()
    assert gauges["ut_steps"] == 3.0
    assert gauges["tokens_trained"] == 3 * 2 * 1 * 24
    scalars = ts.round_host_scalars(clients, ms)
    assert scalars["lm_exit_entropy"] == float(g["lm_exit_entropy"])


DEEP = dict(SMALL, hidden_size=256, intermediate_size=704,
            num_hidden_layers=6, layer_types=["full_attention"] * 6,
            num_attention_heads=8, num_key_value_heads=8, head_dim=32,
            vocab_size=2304)


def test_compiled_round_of_a_looped_model_holds_under_four_and_a_third_trees(
        tmp_path):
    """Arguments and temporaries of the compiled sequential round,
    counted by the compiler (a count, valid on the CPU), at a size
    where activations are negligible beside the parameters (6.0 M of
    them, rows of 8 tokens) and the looped leaves are most of the tree
    (0.80 here, 0.67 in the benchmark's configuration). A single-pass
    model holds 3.80 trees on this backend
    (tests/test_sequential_round.py: three trees and what of a step's
    gradient it keeps). A looped leaf's gradient is a sum over the
    passes, so the scan over passes carries a float32 accumulator of
    every looped leaf through the backward pass, and the update waits
    for it: three trees and one whole gradient (the accumulators 0.80,
    the embedding's and the head's 0.20), 4.12 measured. The bound:
    4.3 trees."""
    spec = write_spec(tmp_path, "deep.json", **DEEP)
    data = token_files.write(str(tmp_path / "data"), 1, clients=CLIENTS,
                             rows_per_client=3, seq_len=8,
                             vocab_size=DEEP["vocab_size"], test_rows=1)
    t = trainer_of(lm_cfg({"data": data}, "sequential", spec=spec))
    server, clients = jax.eval_shape(t.init_state, jax.random.key(0))
    size = lambda tree: sum(x.size * x.dtype.itemsize
                            for x in jax.tree.leaves(tree))
    tree = size(server.params)
    looped = sum(size(v) for k, v in server.params.items()
                 if k.startswith("layer_")) / tree
    assert tree > 2e7 and 0.6 < looped < 0.85
    mem = jax.jit(t.round_fn, donate_argnums=(0, 1)).lower(
        server, clients, t.data, None).compile().memory_analysis()
    held = (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / tree
    assert 4.0 < held < 4.3, (held, looped)


def test_launcher_runs_saves_and_resumes(files, tmp_path):
    from fedtorch_tpu.cli import run_experiment
    run_dir = str(tmp_path / "run")
    first = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=2))
    assert 0.0 <= first["test_top1"] <= 1.0
    with open(os.path.join(run_dir, "checkpoint.json")) as f:
        assert json.load(f)["round"] == 2
    rows = round_rows(run_dir)
    assert [r["round"] for r in rows] == [0, 1]
    assert all(r["tokens_trained"] == 3 * 2 * 24 and r["ut_steps"] == 3
               for r in rows)
    assert all(np.isfinite(r["loss"]) and 0 < r["lm_exit_mass_last"] < 1
               and 0 < r["lm_exit_entropy"] < np.log(3.0) for r in rows)
    # what the rematerialized layers keep (all, on this backend): two
    # layers' seven products a token, three passes, 24 tokens, float32
    assert all(r["lm_kept_product_share"] == 1.0
               and r["lm_kept_residual_bytes"]
               == 2 * (5 * 32 + 2 * 48) * 3 * 24 * 4 for r in rows)
    again = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=3, resume=run_dir))
    assert [r["round"] for r in round_rows(run_dir)] == [0, 1, 2]
    assert 0.0 <= again["test_top1"] <= 1.0


# -- the single-pass model's program --------------------------------------------

# the round's digest (of the text with the counters off its private
# functions' names, which one more traced equation shifts) with nothing
# kept: PR 36's program, whose raw text read ``e54e9767...eace23f57``;
# and with what the chooser keeps where the backend reports no memory
# (all)
OLMO_ROUND_SHA256 = {
    "nothing_kept":
    "b7202d2bbc1d5918a96b9c7ad491495b1c29c817aaddaff38250d8447a202281",
    "chosen":
    "ddbf75df2b943c92d50e73597c419a534ba6d228312fee34b790d41581a341b5",
}


@pytest.mark.parametrize("kept", ["nothing_kept", "chosen"])
def test_the_olmo_cells_lowered_round_is_unchanged(tmp_path, monkeypatch,
                                                   kept):
    """``olmo_hybrid_7b_l4.fedavg_k2_e10``'s round program at the
    cell's own flags and widths (nothing allocated: abstract state, a
    store of 4 rows a client), lowered on the CPU: the text's digest.
    With no product kept it is the digest PR 36 left (which moved it
    on purpose: the delta rule's triangular inverse; PR 35 had left
    its parent's, ``0d16f9ff...da836ce``, in place): naming the
    products' results and handing the checkpoints a policy that keeps
    none moves no operation. PR 38 moved the chosen program's on
    purpose: its layers' checkpoints keep every named product here. A
    change to the shared model file that moves one operation of the
    single-pass path moves both."""
    from benchmark.harness import runner
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.cli import args_to_config, build_parser
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    if kept == "nothing_kept":
        monkeypatch.setattr(hybrid_lm, "_kept_for", lambda s, r, t: ())
    cell = runner.load_cell("olmo_hybrid_7b_l4.fedavg_k2_e10")
    sizes = dict(cell["config_file"]["datagen"], rows_per_client=4,
                 test_rows=1)
    data_dir = token_files.write(str(tmp_path / "d"), 7, **sizes)
    cfg = args_to_config(build_parser().parse_args(runner.launcher_argv(
        cell, 7, data_dir, str(tmp_path / "run"))))
    t = FederatedTrainer(
        cfg, define_model(cfg, batch_size=cfg.data.batch_size),
        make_algorithm(cfg), build_federated_data(cfg).train)
    server, clients = jax.eval_shape(t.init_state, jax.random.key(0))
    text = jax.jit(t.round_fn, donate_argnums=(0, 1)).lower(
        server, clients, t.data, None).as_text()
    text = re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1", text)
    digest = hashlib.sha256(text.encode()).hexdigest()
    assert digest == OLMO_ROUND_SHA256[kept], digest
