"""The language model read from a file (``models/hybrid_lm.py``)
against the plain reference (``benchmark/reference/olmo_hybrid.py``)
at a small specification with both layer kinds; the specification's
loader; initialisation as one program; evaluation on token rows."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import olmo_hybrid as reference
from fedtorch_tpu.models.hybrid_lm import (
    HybridLM, load_spec, param_shapes,
)

SMALL = {
    "vocab_size": 64, "hidden_size": 32, "intermediate_size": 48,
    "num_hidden_layers": 2,
    # more entries than num_hidden_layers: the list is cut to it
    "layer_types": ["linear_attention", "full_attention",
                    "linear_attention", "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 12,
    "linear_conv_kernel_dim": 4, "rms_norm_eps": 1e-6,
    "launcher": {"ignored": True},
}


@pytest.fixture
def spec_file(tmp_path):
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(SMALL))
    return str(path)


def model_of(spec_file, **kw):
    kw = dict(dict(dtype="float32", attention="auto", remat=True), **kw)
    return HybridLM("hybrid_lm", load_spec(spec_file), **kw)


def tokens(shape, seed=0):
    return jnp.asarray(np.random.RandomState(seed).randint(
        0, SMALL["vocab_size"], shape), jnp.int32)


def test_loss_and_gradients_equal_the_reference(spec_file):
    model = model_of(spec_file)
    params = model.init(jax.random.key(1))
    x = tokens((2, 20))
    ref_loss = reference.make_loss(reference.load_spec(spec_file))
    with jax.default_matmul_precision("highest"):
        (loss, acc), grads = jax.jit(jax.value_and_grad(
            lambda p: model.token_loss(p, x), has_aux=True))(params)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref_loss(p, x, None)))(params)
    assert 0.0 <= float(acc) <= 1.0
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    gaps = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(b)) + 1e-12)),
        grads, want_grads)
    assert max(jax.tree.leaves(gaps)) < 1e-4, gaps
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))


def test_remat_and_logits_agree_with_the_loss(spec_file):
    x = tokens((1, 24), seed=3)
    plain = model_of(spec_file, remat=False)
    params = plain.init(jax.random.key(2))
    loss, _ = jax.jit(plain.token_loss)(params, x)
    loss_remat, _ = jax.jit(model_of(spec_file).token_loss)(params, x)
    np.testing.assert_allclose(loss, loss_remat, rtol=1e-6)
    logits = jax.jit(plain.apply)(params, x)
    assert logits.shape == (1, 24, SMALL["vocab_size"])
    logp = jax.nn.log_softmax(logits[:, :-1])
    want = -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))
    np.testing.assert_allclose(loss, want, rtol=1e-6)


def test_bfloat16_operands_keep_float32_parameters(spec_file):
    model = model_of(spec_file, dtype="bfloat16")
    params = model.init(jax.random.key(1))
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype("float32")}
    x = tokens((1, 32))
    loss16, _ = jax.jit(model.token_loss)(params, x)
    loss32, _ = jax.jit(model_of(spec_file).token_loss)(params, x)
    assert loss16.dtype == jnp.float32
    assert abs(float(loss16) - float(loss32)) < 0.02 * float(loss32)


def test_initialisation_is_one_program(spec_file):
    model = model_of(spec_file)
    key = jax.random.key(5)
    eqns = jax.make_jaxpr(model.init)(key).eqns
    assert [e.primitive.name for e in eqns] in (["pjit"], ["jit"])
    got = jax.tree.map(lambda v: tuple(v.shape), model.init(key))
    assert got == param_shapes(model.spec)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax.tree.leaves(model.init(key)), jax.tree.leaves(model.init(key))))


def test_the_benchmarks_configuration_counts_its_parameters():
    """The published widths with the cut the file states: counted from
    shapes, nothing allocated."""
    path = "benchmark/configs/olmo_hybrid_7b_l4.json"
    spec = load_spec(path)
    with open(path) as f:
        doc = json.load(f)
    assert spec.layer_types == ("linear_attention",) * 3 + (
        "full_attention",)
    assert len(doc["layer_types"]) == doc["published"]["num_hidden_layers"]
    shapes = jax.tree.leaves(param_shapes(spec),
                             is_leaf=lambda s: isinstance(s, tuple))
    total = sum(int(np.prod(s)) for s in shapes)
    assert total == doc["parameters"]["total"] == 928862196


@pytest.mark.parametrize("change,match", [
    ({"layer_types": ["linear_attention", "sliding_attention"],
      "num_hidden_layers": 2}, "layer_types"),
    ({"num_hidden_layers": 5}, "layer_types"),
    ({"linear_num_value_heads": 4}, "grouped value heads"),
    ({"num_key_value_heads": 3}, "no multiple of num_key_value_heads"),
    ({"tie_word_embeddings": True}, "tied"),
    ({"hidden_size": None}, "lacks"),
])
def test_specification_refusals(tmp_path, change, match):
    doc = dict(SMALL, **change)
    doc = {k: v for k, v in doc.items() if v is not None}
    path = tmp_path / "bad.json"
    path.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match=match):
        load_spec(str(path))


@pytest.mark.parametrize("change,mixer", [
    # grouped key/value heads: k and v of 2 heads of 8, a norm over each
    ({"num_key_value_heads": 2},
     {"wq": (32, 32), "wk": (32, 16), "wv": (32, 16), "wo": (32, 32),
      "q_norm": (32,), "k_norm": (16,)}),
    # a head size of its own: attention 4 x 16 = 64 wide on a stream of 32
    ({"head_dim": 16},
     {"wq": (32, 64), "wk": (32, 64), "wv": (32, 64), "wo": (64, 32),
      "q_norm": (64,), "k_norm": (64,)}),
])
def test_grouped_heads_and_a_free_head_size_are_read(tmp_path, change,
                                                     mixer):
    """What the loader refused until PR 39: read, and the layer equals
    attention over key and value heads repeated to the query heads'
    count."""
    path = tmp_path / "spec.json"
    path.write_text(json.dumps(dict(SMALL, **change)))
    model = model_of(str(path))
    spec = model.spec
    assert (spec.kv_heads, spec.head_size) == (
        change.get("num_key_value_heads", 4), change.get("head_dim", 8))
    assert param_shapes(spec)["layer_1"]["mixer"] == mixer
    params = model.init(jax.random.key(3))
    x = tokens((2, 12), seed=4)
    loss, _ = jax.jit(model.token_loss)(params, x)
    # the same weights with every key and value head written out
    group = 4 // spec.kv_heads
    wide = dict(dict(SMALL, **change), num_key_value_heads=4)
    path.write_text(json.dumps(wide))
    full = model_of(str(path))
    hd = spec.head_size
    spread = lambda w: jnp.repeat(
        w.reshape(w.shape[:-1] + (spec.kv_heads, hd)), group,
        axis=-2).reshape(w.shape[:-1] + (4 * hd,))
    mixed = dict(params["layer_1"]["mixer"])
    mixed.update(wk=spread(mixed["wk"]), wv=spread(mixed["wv"]))
    # the Olmo block norms all of k at once: over repeated heads the
    # mean square is the same, and the scale repeats with the heads
    mixed["k_norm"] = spread(mixed["k_norm"])
    want, _ = jax.jit(full.token_loss)(
        dict(params, layer_1=dict(params["layer_1"], mixer=mixed)), x)
    np.testing.assert_allclose(loss, want, rtol=2e-6)


def test_define_model_needs_the_file():
    from fedtorch_tpu.config import ExperimentConfig, ModelConfig
    from fedtorch_tpu.models import define_model
    cfg = ExperimentConfig(model=ModelConfig(arch="hybrid_lm"))
    with pytest.raises(ValueError, match="--model_spec"):
        define_model(cfg)


def test_evaluation_reports_next_token_loss_and_top1(spec_file):
    from fedtorch_tpu.parallel.evaluate import evaluate
    model = model_of(spec_file)
    params = model.init(jax.random.key(1))
    rows = np.asarray(tokens((3, 20), seed=4))
    res = jax.device_get(evaluate(model, params, rows,
                                  np.zeros(3, np.int32)))
    loss_of = jax.jit(lambda row: model.token_loss(params, row)[0])
    want = np.mean([float(loss_of(rows[i:i + 1])) for i in range(3)])
    np.testing.assert_allclose(res.loss, want, rtol=1e-5)
    assert 0.0 <= float(res.top1) <= float(res.top5) <= 1.0
