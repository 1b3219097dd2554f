"""The latent-attention, shared-expert model read from a file
(``models/hybrid_lm.py`` on a ``deepseek_v3`` file, with
``ops/routed_experts.py:route``'s sigmoid scoring and selection bias
and the flash kernel's value head size) against the plain reference
(``benchmark/reference/kanana2.py``) at a small specification: one
dense layer and two expert layers, 4 heads of 16 + 8 on value heads of
16, a latent of 16 + 8, 8 routed experts of which 4 are held, 3 a
token, a shared expert of two widths, 24-token rows. The shares add up
to the whole layer; the choice is by score plus bias and the gates by
score; the balance part moves the biases by the published step and
nothing else; the other cells' programs are where they were."""
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.datagen import tokens as token_files
from benchmark.reference import _ops, kanana2 as reference
from fedtorch_tpu.config import OptimConfig
from fedtorch_tpu.core import optim
from fedtorch_tpu.models import hybrid_lm
from fedtorch_tpu.models.hybrid_lm import HybridLM, load_spec, param_shapes
from fedtorch_tpu.ops import routed_experts
from fedtorch_tpu.ops.pallas.flash_attention import flash_attention
from test_sequential_round import (
    gauges_of, lm_cfg, round_rows, trainer_of, with_field_names,
)

SMALL = {
    "model_type": "deepseek_v3", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 96, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 1000000, "rope_scaling": None,
    "rope_interleave": True, "kv_lora_rank": 16, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
    "v_head_dim": 16, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "n_routed_experts": 4, "n_shared_experts": 2,
    "num_experts_per_tok": 3, "moe_intermediate_size": 24,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "n_group": 1, "topk_group": 1, "first_expert_held": 2,
    "published": {"n_routed_experts": 8}, "balance_loss_coef": 0.05,
    "tie_word_embeddings": False, "attention_bias": False,
    "launcher": {"ignored": True},
}
CLIENTS = 6
CONFIG = "benchmark/configs/kanana2_30b_a3b_l5.json"


def write_spec(tmp_path, name="spec.json", **change):
    path = tmp_path / name
    doc = {k: v for k, v in dict(SMALL, **change).items() if v is not ...}
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


def with_biases(params, seed=3, size=0.3):
    """The seeded parameters with routers' biases that change the
    choice (seeded they are zero)."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: x + size * jax.random.normal(
            jax.random.key(seed), x.shape)
        if path[-1].key == "router_bias" else x, params)


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


def is_bias(path) -> bool:
    return path[-1].key == "router_bias"


# -- against the reference ------------------------------------------------------

@pytest.mark.parametrize("remat", [True, False])
def test_loss_and_gradients_equal_the_reference(spec_file, remat):
    model = model_of(spec_file, remat=remat)
    params = with_biases(model.init(jax.random.key(1)))
    x = tokens((2, 24))
    spec = reference.load_spec(spec_file)
    with jax.default_matmul_precision("highest"):
        (loss, (acc, parts)), grads = loss_and_grads(model, params, x)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.make_loss(spec)(p, x, None)))(params)
        _, ce, balance = reference.objective(params, x, spec)
    assert 0.0 <= float(acc) <= 1.0
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(parts["ce"], ce, rtol=1e-6)
    assert float(parts["balance_loss"]) == 0.0 and float(balance) != 0.0
    worst, gaps = worst_gap(grads, want_grads)
    assert worst < 1e-5, gaps
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))
    # the counters: 48 tokens x 3 a token over 8 experts, 4 of them held
    assert 0 < float(parts["moe_pairs"]) < 144
    assert 1.0 <= float(parts["router_load_max_over_mean"]) <= 8.0
    assert float(parts["router_bias_abs_max"]) == pytest.approx(max(
        float(jnp.max(jnp.abs(params[f"layer_{i}"]["mlp"]["router_bias"])))
        for i in (1, 2)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_operands_stay_inside_a_band(spec_file, seed):
    """bfloat16 operands move a token across the router's top-3 now and
    then (the router's own product stays float32, so only its input
    differs). The band: the loss within 1e-3 of itself, the whole
    gradient within a cosine of 0.998 and 6 % in norm of the float32
    one, every leaf within 75 % of its largest entry (a flipped choice
    moves one expert's rows), the biases' gradient the same signs but
    where a load sits at the mean, parameters and gradients float32."""
    model = model_of(spec_file, dtype="bfloat16")
    params = model.init(jax.random.key(1 + seed))
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype("float32")}
    x = tokens((2, 24), seed=seed)
    (loss16, _), grads16 = loss_and_grads(model, params, x)
    (loss32, _), grads32 = loss_and_grads(model_of(spec_file), params, x)
    assert loss16.dtype == jnp.float32
    assert abs(float(loss16) - float(loss32)) < 1e-3 * float(loss32)
    flat = lambda g: jnp.concatenate([
        v.reshape(-1) for p, v in jax.tree_util.tree_leaves_with_path(g)
        if not is_bias(p)])
    a, b = flat(grads16), flat(grads32)
    assert float(a @ b / jnp.linalg.norm(a) / jnp.linalg.norm(b)) > 0.998
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.06
    keep = lambda g: {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_leaves_with_path(g)
                      if not is_bias(p)}
    worst, gaps = worst_gap(keep(grads16), keep(grads32))
    assert worst < 0.75, gaps
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads16))
    for i in (1, 2):
        g16, g32 = (g[f"layer_{i}"]["mlp"]["router_bias"]
                    for g in (grads16, grads32))
        assert int(jnp.sum(g16 != g32)) <= 2, (g16, g32)


def test_remat_and_logits_agree_with_the_loss(spec_file):
    x = tokens((1, 24), seed=3)
    plain = model_of(spec_file, remat=False)
    params = with_biases(plain.init(jax.random.key(2)))
    loss, _, parts = jax.jit(plain.token_loss_parts)(params, x)
    loss_remat, _ = jax.jit(model_of(spec_file).token_loss)(params, x)
    np.testing.assert_allclose(loss, loss_remat, rtol=1e-6)
    logits = jax.jit(plain.apply)(params, x)
    assert logits.shape == (1, 24, SMALL["vocab_size"])
    logp = jax.nn.log_softmax(logits[:, :-1])
    want = -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))
    # evaluation reads CE; the balance part adds nothing to the value
    np.testing.assert_allclose(parts["ce"], want, rtol=1e-6)
    assert float(loss) == float(parts["ce"])


@pytest.mark.parametrize("kept", ["all", "half", "none"])
def test_gradients_are_the_same_whatever_is_kept(spec_file, monkeypatch,
                                                 kept):
    """Whatever the layers' checkpoints keep (all the named products,
    the cheapest-to-run-again half left out, nothing), loss and
    gradients are the plain layers' within the tolerance the model's
    own test holds ``remat`` to (bit for bit but for the rotary turn's
    multiply-add, which this backend contracts or not by what it is
    fused with: tests/test_kept_products.py), and the backward pass
    runs again the products that were not kept and no other."""
    from test_kept_products import rematted_products
    model = model_of(spec_file)
    params = with_biases(model.init(jax.random.key(4)))
    x = tokens((2, 24), seed=5)
    order = hybrid_lm.kept_products(model.spec, 2, 24, None)
    names = {"all": order, "half": order[:len(order) // 2], "none": ()}[kept]
    (want, _), want_grads = loss_and_grads(
        model_of(spec_file, remat=False), params, x)
    monkeypatch.setattr(hybrid_lm, "_kept_for", lambda s, r, t: names)
    (loss, _), grads = loss_and_grads(model, params, x)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    worst, gaps = worst_gap(grads, want_grads)
    assert worst < 1e-5, gaps
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda p: model.token_loss(p, x)[0]))(params)
    layers = [hybrid_lm.layer_products(model.spec, "latent_attention",
                                       dense) for dense in (True, False,
                                                            False)]
    assert [len(products) for products in layers] == [7, 11, 11]
    # ... of those the counter sees: the experts' grouped products are
    # ``ragged_dot``s, and a pre-norm layer's closing product feeds the
    # stream alone, so no backward pass needs its result again
    unseen = ("mlp.gate", "mlp.up", "mlp.down", "dense.down", "shared.down")
    assert rematted_products(jaxpr.jaxpr) == sum(
        1 for products in layers for n in products
        if n not in names and n not in unseen)


# -- the expert layer -------------------------------------------------------------

def expert_case(seed=0, tokens_=40, routed=8, d=32, f=24):
    keys = jax.random.split(jax.random.key(seed), 9)
    normal = lambda i, shape, size=0.3: size * jax.random.normal(
        keys[i], shape)
    u = normal(0, (tokens_, d), 1.0)
    full = {"router": normal(1, (d, routed), 1.0),
            "router_bias": normal(2, (routed,), 0.5),
            "gate": normal(3, (routed, d, f)), "up": normal(4, (routed, d, f)),
            "down": normal(5, (routed, f, d)),
            "shared": {"gate": normal(6, (d, 2 * f)),
                       "up": normal(7, (d, 2 * f)),
                       "down": normal(8, (2 * f, d))}}
    return u, full


def share_of(full, first, held):
    cut = lambda w: w[first:first + held]
    return dict(full, gate=cut(full["gate"]), up=cut(full["up"]),
                down=cut(full["down"]))


def layer_spec(first, held, routed=8, per_token=3):
    return hybrid_lm.HybridSpec(
        vocab_size=8, hidden_size=32, intermediate_size=0,
        layer_types=("latent_attention",), num_attention_heads=1,
        linear_num_key_heads=0, linear_num_value_heads=0,
        linear_key_head_dim=0, linear_value_head_dim=0,
        linear_conv_kernel_dim=0, rms_norm_eps=1e-6, prenorm=True,
        experts=hybrid_lm.Experts(
            routed, held, first, per_token, 24, True, scoring="sigmoid",
            scale=2.448, biased=True, balance=0.05, shared=48))


def program_layer(p, u, first, held):
    out, counters = hybrid_lm._experts(p, u[None], layer_spec(first, held),
                                       jnp.float32)
    return out[0], counters


def reference_layer(p, u, first):
    return reference.experts(p, u, {
        "num_experts_per_tok": 3, "norm_topk_prob": True,
        "routed_scaling_factor": 2.448, "first_expert_held": first,
        "routed_experts": 8}, _ops.identity)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_whole_layer(held):
    """The guide's test of the cut: the routed parts that all the
    shares give (8 / ``held`` chips, router and bias counted on each,
    the gates normalised over all chosen experts and scaled), summed,
    plus the shared expert's result counted ONCE (every share computes
    it whole), are the uncut reference's layer."""
    u, full = expert_case()
    with jax.default_matmul_precision("highest"):
        whole, _ = reference_layer(full, u, 0)
        shared = reference.swiglu(full["shared"], u, _ops.identity)
        parts = [program_layer(share_of(full, first, held), u, first, held)
                 for first in range(0, 8, held)]
        same = [reference_layer(share_of(full, first, held), u, first)[0]
                for first in range(0, 8, held)]
    routed = sum(o - shared for o, _ in parts)
    np.testing.assert_allclose(routed + shared, whole, atol=3e-5)
    for (o, _), r in zip(parts, same):
        np.testing.assert_allclose(o, r, atol=3e-5)
    # every token-expert pair is computed on exactly one share, and
    # every share counts the same load over all 8 experts
    assert sum(float(c["pairs"]) for _, c in parts) == u.shape[0] * 3
    assert len({float(c["router_load_max_over_mean"])
                for _, c in parts}) == 1


def test_the_choice_is_by_score_plus_bias_and_the_gates_by_score():
    """A bias that changes the choice leaves the formula of the chosen
    experts' gates alone: ``scale x s_e / (sum of the chosen s +
    1e-20)`` from the unbiased sigmoids; no gradient reaches the bias
    and the logits' gradient is the unbiased scores'."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(50, 8), jnp.float32)
    bias = jnp.asarray([3.0, 0, 0, 0, 0, 0, 0, -3.0], jnp.float32)
    route = lambda z, b: routed_experts.route(
        z, 3, True, scoring="sigmoid", bias=b, scale=2.448)
    gates, chosen = route(logits, bias)
    plain, plain_chosen = route(logits, jnp.zeros(8))
    s = jax.nn.sigmoid(logits)
    want = jax.lax.top_k(s + bias, 3)[1]
    np.testing.assert_array_equal(chosen, want)
    assert bool(jnp.all(jnp.any(chosen == 0, axis=1)))      # +3 always in
    assert not bool(jnp.any(chosen == 7))                   # -3 never
    assert bool(jnp.any(jnp.sort(chosen) != jnp.sort(plain_chosen)))
    picked = jnp.take_along_axis(s, chosen, axis=1)
    np.testing.assert_allclose(
        gates, 2.448 * picked / (picked.sum(1, keepdims=True) + 1e-20),
        rtol=1e-6)
    np.testing.assert_allclose(gates.sum(1), 2.448, rtol=1e-5)
    np.testing.assert_allclose(plain.sum(1), 2.448, rtol=1e-5)
    g_logits, g_bias = jax.grad(
        lambda z, b: jnp.sum(route(z, b)[0] ** 2), argnums=(0, 1))(
        logits, bias)
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0
    assert float(jnp.max(jnp.abs(g_logits))) > 0.0
    with pytest.raises(ValueError, match="scoring"):
        routed_experts.route(logits, 3, True, scoring="tanh")


def test_route_on_a_softmax_file_returns_what_it_returned():
    """The softmax router (the keye file's) as PR 39 wrote it, bit for
    bit, and the same jaxpr: the new arguments' defaults add no
    operation."""
    logits = jnp.asarray(np.random.RandomState(1).randn(40, 16),
                         jnp.float32)

    def before(logits, per_token, normalise):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gates, experts = jax.lax.top_k(probs, per_token)
        if normalise:
            gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
        return gates, experts

    for normalise in (True, False):
        got = routed_experts.route(logits, 4, normalise)
        want = before(logits, 4, normalise)
        jax.tree.map(np.testing.assert_array_equal, got, want)
        assert str(jax.make_jaxpr(
            lambda z: routed_experts.route(z, 4, normalise))(logits)) \
            == str(jax.make_jaxpr(lambda z: before(z, 4, normalise))(logits))


def test_the_balance_part_moves_the_biases_alone_and_by_the_step(
        spec_file):
    """``L_B`` has value zero; under the launcher's SGD (lr 0.02, the
    cell's) a step moves every ``b_e`` by exactly ``lr x u x sign(
    mean(c) - c_e)`` (0.001 at ``u`` 0.05), ``c`` the step's pairs over
    all 8 experts; ``L_B`` reaches no other leaf and ``CE`` reaches
    every leaf but the biases."""
    model = model_of(spec_file)
    params = with_biases(model.init(jax.random.key(6)), size=0.1)
    x = tokens((2, 24), seed=7)
    part = lambda name: jax.jit(jax.grad(
        lambda p: model.token_loss_parts(p, x)[2][name]))(params)
    for name, own in (("balance_loss", True), ("ce", False)):
        for path, g in jax.tree_util.tree_leaves_with_path(part(name)):
            moved = float(jnp.max(jnp.abs(g))) > 0
            assert moved == (is_bias(path) == own), (name, path)
    (loss, (_, parts)), grads = loss_and_grads(model, params, x)
    assert float(parts["balance_loss"]) == 0.0
    assert float(loss) == float(parts["ce"])
    cfg = OptimConfig(lr=0.02, weight_decay=0.0)
    stepped, _ = optim.local_step(
        params, grads, optim.init_opt_state(params, cfg, lean=True), 0.02,
        cfg)
    # the loads of the step, from the reference's own router
    spec = reference.load_spec(spec_file)
    h = params["embed"][x]
    for i in range(3):
        p = params[f"layer_{i}"]
        if i:
            a = h + reference.attention(
                p["mixer"], reference.rms_norm(h, p["mixer_norm"], 1e-6),
                spec, _ops.identity)
            u = reference.rms_norm(a, p["mlp_norm"], 1e-6)
            s = jax.nn.sigmoid(u @ p["mlp"]["router"])
            chosen = jax.lax.top_k(s + p["mlp"]["router_bias"], 3)[1]
            c = jnp.bincount(chosen.reshape(-1), length=8)
            want = p["mlp"]["router_bias"] + jnp.float32(0.02) * (
                jnp.float32(0.05) * jnp.sign(jnp.mean(c) - c))
            np.testing.assert_array_equal(
                stepped[f"layer_{i}"]["mlp"]["router_bias"], want)
            assert float(jnp.max(jnp.abs(
                want - p["mlp"]["router_bias"]))) == pytest.approx(
                1e-3, rel=1e-4)
        h, _ = reference.layer(p, h, i < 1, spec, _ops.identity)
    frozen = [path for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(stepped)) if bool(jnp.all(a == b))]
    assert frozen == []


# -- latent attention -------------------------------------------------------------

def published_rotary(q, k, theta):
    """``apply_rotary_pos_emb_interleave`` of the published code, on
    [B, T, H, d]: the even elements moved in front of the odd ones,
    then rotate-half with ``cos``/``sin`` of ``[freqs | freqs]``."""
    def turn(x):
        B, T, H, d = x.shape
        x = x.reshape(B, T, H, d // 2, 2).swapaxes(-1, -2).reshape(B, T, H,
                                                                   d)
        inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float32) / d)
        ang = np.arange(T, dtype=np.float32)[:, None] * inv[None]
        ang = np.concatenate([ang, ang], -1)[None, :, None, :]
        half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
        return x * np.cos(ang) + half * np.sin(ang)
    return turn(q), turn(k)


def test_the_rotary_function_is_the_published_interleaved_one():
    """The program's layout (``pairs_apart`` then the rotate-half turn)
    IS the published function's output, and the reference's in-place
    turn of the pairs gives the same dot products."""
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(2, 12, 4, 8), jnp.float32)
    k = jnp.asarray(rng.randn(2, 12, 1, 8), jnp.float32)
    rope = hybrid_lm.rotary_tables(jnp.arange(12), 8, 1e6)
    got_q = hybrid_lm._rotate(hybrid_lm.pairs_apart(q), rope)
    got_k = hybrid_lm._rotate(hybrid_lm.pairs_apart(k), rope)
    want_q, want_k = published_rotary(q, k, 1e6)
    np.testing.assert_allclose(got_q, want_q, atol=1e-6)
    np.testing.assert_allclose(got_k, want_k, atol=1e-6)
    ref_q = reference.rotary_interleaved(q, 1e6)
    ref_k = reference.rotary_interleaved(k, 1e6)
    np.testing.assert_allclose(
        jnp.einsum("bqhd,bkd->bhqk", ref_q, ref_k[:, :, 0]),
        jnp.einsum("bqhd,bkd->bhqk", want_q, want_k[:, :, 0]), atol=1e-5)
    # pair i is elements (2i, 2i + 1): a position's turn mixes those two
    one = jnp.zeros((1, 2, 1, 8)).at[0, 1, 0, 2].set(1.0)
    turned = reference.rotary_interleaved(one, 1e6)[0, 1, 0]
    assert set(np.flatnonzero(np.asarray(turned))) == {2, 3}


def dense_attention(q, k, v):
    T = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool))[None, None], s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("T,block", [(256, 128), (192, 64)])
def test_the_flash_kernel_takes_a_value_head_size_of_its_own(T, block):
    """``flash_attention(force='interpret')`` at the model's head sizes,
    192 for queries and keys and 128 for values, against the dense
    oracle: forward and the three gradients (the chunked backward), no
    operand padded; the result is 128 wide."""
    rng = np.random.RandomState(T)
    q, k = (jnp.asarray(rng.randn(1, T, 2, 192) * 0.3, jnp.float32)
            for _ in range(2))
    v = jnp.asarray(rng.randn(1, T, 2, 128), jnp.float32)
    w = jnp.asarray(rng.randn(1, T, 2, 128), jnp.float32)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=block, block_k=block,
        force="interpret")
    with jax.default_matmul_precision("highest"):
        out = flash(q, k, v)
        want = dense_attention(q, k, v)
        got_g = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(
            q, k, v)
        want_g = jax.grad(lambda *a: jnp.sum(dense_attention(*a) * w),
                          (0, 1, 2))(q, k, v)
    assert out.shape == (1, T, 2, 128)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for a, b in zip(got_g, want_g):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=5e-5)


def test_the_flash_kernel_lowers_for_the_chip_at_the_cells_heads():
    """Mosaic takes blocks whose last dimension is the head's whole 192
    (one and a half lane tiles) and a value tile of 128 beside them:
    the lowering for a TPU, from the CPU (the compile is the chip's)."""
    import fedtorch_tpu.ops.pallas.flash_attention as fa
    q = jax.ShapeDtypeStruct((1, 1024, 2, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1024, 2, 128), jnp.bfloat16)

    def fwd(q, k, v):
        (q3, k3, v3), _, scale, bq, bk, _ = fa._prep(q, k, v, None, 512,
                                                     512, None)
        return fa._flash3(q3, k3, v3, scale, True, bq, bk, True)

    text = jax.jit(fwd).trace(q, q, v).lower(
        lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_the_model_runs_the_same_function_on_both_attention_paths(
        spec_file):
    """'flash' (off a TPU: the kernel's dense oracle with its chunked
    backward) and 'dense' give the same loss and gradients at heads of
    24 on 16."""
    x = tokens((2, 24), seed=9)
    params = model_of(spec_file).init(jax.random.key(8))
    with jax.default_matmul_precision("highest"):
        (a, _), ga = loss_and_grads(model_of(spec_file, attention="dense"),
                                    params, x)
        (b, _), gb = loss_and_grads(model_of(spec_file, attention="flash"),
                                    params, x)
    np.testing.assert_allclose(a, b, rtol=1e-6)
    keep = lambda g: [v for p, v in jax.tree_util.tree_leaves_with_path(g)
                      if not is_bias(p)]
    assert worst_gap(keep(ga), keep(gb))[0] < 1e-4
    for tokens_ in (24, 4096):      # on the CPU
        gauges = model_of(spec_file).trace_gauges(1, tokens_)
        assert gauges["lm_attention_kernel_share"] == 0.0
        assert gauges["lm_attention_backward_kernel_share"] == 0.0


@pytest.mark.parametrize("remat", [True, False])
def test_the_model_takes_the_kernels_in_both_directions(
        spec_file, monkeypatch, remat):
    """'flash' with the kernels in the interpreter, forward and backward
    (what a TPU compiles), against the dense model on a 256-token row:
    two query and two key tiles a head, under the layer's checkpoint
    and without it; one backward kernel a layer."""
    import fedtorch_tpu.ops.pallas.flash_attention as fa
    built = []
    bwd_pallas = fa._bwd_pallas

    def counted(res, *a, **kw):
        built.append(res[0].shape)
        return bwd_pallas(res, *a, **kw)

    monkeypatch.setattr(fa, "_backend", lambda bq, bk, force: None)
    monkeypatch.setattr(fa, "_bwd_pallas", counted)
    x = tokens((1, 256), seed=11)
    params = model_of(spec_file).init(jax.random.key(8))
    with jax.default_matmul_precision("highest"):
        (a, _), ga = loss_and_grads(
            model_of(spec_file, attention="dense", remat=remat), params, x)
        (b, _), gb = loss_and_grads(
            model_of(spec_file, attention="flash", remat=remat), params, x)
    assert built == [(4, 256, 24)] * SMALL["num_hidden_layers"]
    np.testing.assert_allclose(a, b, rtol=1e-6)
    keep = lambda g: [v for p, v in jax.tree_util.tree_leaves_with_path(g)
                      if not is_bias(p)]
    assert worst_gap(keep(ga), keep(gb))[0] < 1e-4


def test_the_backward_counter_follows_the_backward_rule(spec_file,
                                                        monkeypatch):
    """On a TPU both counters read 1 from 4096 tokens on ('auto'), and
    0 below, where the layer takes the dense form."""
    import fedtorch_tpu.ops.pallas.flash_attention as fa
    monkeypatch.setattr(fa, "on_tpu", lambda: True)
    monkeypatch.setattr(hybrid_lm, "on_tpu", lambda: True)
    model = model_of(spec_file)
    share, backward = "lm_attention_kernel_share", \
        "lm_attention_backward_kernel_share"
    assert model.trace_gauges(1, 4096)[share] == 1.0
    assert model.trace_gauges(1, 4096)[backward] == 1.0
    assert model.trace_gauges(1, 2048)[backward] == 0.0
    dense = model_of(spec_file, attention="dense")
    assert dense.trace_gauges(1, 4096)[backward] == 0.0


# -- the specification ------------------------------------------------------------

def test_the_benchmarks_configuration_counts_its_parameters():
    """The published widths with the cut the file states: counted from
    shapes, nothing allocated; every number of the catalog's config is
    in the file under its key but the three the file lists."""
    spec = load_spec(CONFIG)
    with open(CONFIG) as f:
        doc = json.load(f)
    assert spec.layer_types == ("latent_attention",) * 5 and spec.prenorm
    assert not spec.head_norm and spec.selection is None
    assert spec.latent == hybrid_lm.Latent(512, 128, 64, 128, True)
    assert spec.experts == hybrid_lm.Experts(
        128, 16, 0, 6, 768, True, scoring="sigmoid", scale=2.448,
        biased=True, balance=0.05, shared=1536, dense_first=1)
    assert doc["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert doc["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 128,
                                "vocab_size": 128256}
    shapes = param_shapes(spec)
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(
        t, is_leaf=lambda s: isinstance(s, tuple)))
    counts = doc["parameters"]
    assert count(shapes["layer_0"]["mixer"]) == counts["attention"] \
        == 12582912 + 1179648 + 512 + 4194304 + 8388608
    assert count(shapes["layer_0"]) == counts["dense_layer"] == 64098816
    mlp = shapes["layer_1"]["mlp"]
    assert mlp["router"] == (2048, 128) and mlp["router_bias"] == (128,)
    assert mlp["gate"] == (16, 2048, 768) and mlp["down"] == (16, 768, 2048)
    assert count(mlp["shared"]) == counts["shared_expert"] == 9437184
    assert count(shapes["layer_4"]) == counts["expert_layer"] == 111547008
    assert count(shapes) == counts["total"] == 575955968 \
        == 64098816 + 4 * 111547008 + 2 * 32833536 + 2048
    # under the budget's names: what each layer's checkpoint may keep,
    # and the chooser's order (the longest inner dimension first: the
    # latent's up-projection is the cheapest plain product to run again)
    latent = {"mixer.q", "mixer.kv_a", "mixer.kv_b", "mixer.o"}
    assert set(hybrid_lm.layer_products(spec, "latent_attention", True)) \
        == latent | {"dense.gate", "dense.up", "dense.down"}
    assert set(hybrid_lm.layer_products(spec, "latent_attention", False)) \
        == latent | {"mlp.router", "mlp.gate", "mlp.up", "mlp.down",
                     "shared.gate", "shared.up", "shared.down"}
    order = hybrid_lm.kept_products(spec, 1, 4096, None)
    assert order[:2] == ("dense.down", "mixer.o")
    # (the experts' names hold a row block's rows, twice the expected
    # pairs: half a plain product's FLOPs a byte kept, so gate and up,
    # 2048 deep, come before the latent's up-projection, 512 deep)
    assert order[-4:] == ("mlp.gate", "mlp.up", "mixer.kv_b", "mlp.down")
    assert order.index("shared.down") == len(order) - 5
    full = hybrid_lm.kept_counters(spec, 4096, order)
    assert full["lm_kept_product_share"] == 1.0
    # a token's kept float32 results: 5 attention sublayers, the dense
    # layer, 4 x (router, the first row block of an expert layer: 6144
    # rows of the buffer's 24 576 at 4096 tokens, 1.5 a token and not
    # the buffer's 6, the shared expert)
    floats = 5 * (6144 + 576 + 8192 + 2048) + 2 * 6144 + 2048 \
        + 4 * (128 + 1.5 * (2 * 768 + 2048) + 2 * 1536 + 2048)
    assert full["lm_kept_residual_bytes"] == 4.0 * floats * 4096
    stats = {"bytes_limit": 16 << 30, "bytes_in_use": 5 << 30}
    budget = hybrid_lm.residual_budget(spec, 1, 4096, stats)
    assert 0 < budget < (16 - 5) * (1 << 30) - 2 * 4 * 575955968
    assert set(hybrid_lm.kept_products(spec, 1, 4096, budget)) <= set(order)
    assert hybrid_lm.kept_products(spec, 1, 4096, 0) == ()


@pytest.mark.parametrize("change,match", [
    ({"q_lora_rank": 1536}, "q_lora_rank"),
    ({"n_group": 8, "topk_group": 4}, "grouped routing"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"scoring_func": "tanh"}, "scoring_func"),
    ({"topk_method": "group_limited_greedy"}, "topk_method"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"kv_lora_rank": ...}, "kv_lora_rank"),
    ({"n_shared_experts": ...}, "n_shared_experts"),
    ({"qk_rope_head_dim": 7}, "odd"),
    ({"first_k_dense_replace": 4}, "first_k_dense_replace"),
    ({"first_expert_held": 6}, "do not lie"),
    ({"rope_scaling": {"type": "yarn", "factor": 40}}, "rope_scaling"),
    ({"layer_types": ["full_attention"] * 3}, "latent_attention"),
    ({"model_type": "KeyeVL2",
      "layer_types": ["latent_attention"] * 3}, "latent_attention"),
])
def test_specification_refusals_by_name(tmp_path, change, match):
    with pytest.raises(ValueError, match=match):
        load_spec(write_spec(tmp_path, **change))


def test_a_softmax_scored_file_of_the_family_routes_by_probability(
        tmp_path):
    """``scoring_func`` ``softmax`` (the family's other scoring): the
    same layer with probabilities for scores, the bias and the balance
    part as they are."""
    model = model_of(write_spec(tmp_path, scoring_func="softmax"))
    params = with_biases(model.init(jax.random.key(0)))
    (loss, (_, parts)), grads = loss_and_grads(model, params,
                                               tokens((1, 24)))
    assert float(parts["balance_loss"]) == 0.0 and np.isfinite(float(loss))
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(grads))


# -- in the engine ----------------------------------------------------------------

@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("kanana")
    data = token_files.write(str(root / "data"), 7, clients=CLIENTS,
                             rows_per_client=5, seq_len=24,
                             vocab_size=SMALL["vocab_size"], test_rows=3)
    return {"spec": write_spec(root), "data": data}


def test_sequential_round_reports_the_routers_gauges(files):
    """The sequential round of the launcher's engine: the loss parts
    reach the round's metrics and its one scalar fetch; the biases move
    from zero by at most two steps of 0.001 x lr / 0.02."""
    t = trainer_of(lm_cfg(files, "sequential"))
    server, clients = t.init_state(jax.random.key(3))
    start = jax.device_get(server.params)
    for _ in range(2):
        server, clients, m = t.run_round(server, clients)
    g = gauges_of(t, m)
    assert float(g["lm_balance_loss"]) == 0.0
    assert 1.0 <= float(g["lm_router_load_max_over_mean"]) <= 8.0
    assert 0.0 < float(g["lm_router_bias_abs_max"]) < 1e-3
    assert 0 < float(g["lm_moe_pairs_local"]) < 72
    assert "lm_index_loss" not in g and "lm_exit_entropy" not in g
    gauges = t.telemetry_gauges()
    assert gauges["lm_attention_kernel_share"] == 0.0
    assert gauges["lm_attention_backward_kernel_share"] == 0.0
    assert "lm_selected_share" not in gauges
    scalars = t.round_host_scalars(clients, m)
    assert scalars["lm_router_bias_abs_max"] == float(
        g["lm_router_bias_abs_max"])
    moved = jax.tree.map(lambda a, b: bool(np.any(np.asarray(a) != b)),
                         jax.device_get(server.params), start)
    assert all(jax.tree.leaves(moved)), moved


def test_launcher_rounds_evaluation_save_and_resume(files, tmp_path):
    from fedtorch_tpu.cli import run_experiment
    run_dir = str(tmp_path / "run")
    result = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir))
    assert 0.0 <= result["test_top1"] <= 1.0
    rows = round_rows(run_dir)
    assert [r["round"] for r in rows] == [0, 1]
    assert all(np.isfinite(r["loss"]) and "eval_s" in r
               and r["tokens_trained"] == 3 * 2 * 24
               and r["lm_balance_loss"] == 0.0
               and r["lm_router_load_max_over_mean"] >= 1.0
               and 0.0 < r["lm_router_bias_abs_max"] < 1e-3
               and r["lm_attention_kernel_share"] == 0.0
               and r["lm_attention_backward_kernel_share"] == 0.0
               and 0 < r["lm_moe_pairs_local"] < 72
               and r["dropped"] == 0 for r in rows)
    again = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=3, resume=run_dir))
    assert [r["round"] for r in round_rows(run_dir)] == [0, 1, 2]
    assert 0.0 <= again["test_top1"] <= 1.0


# -- the other cells' programs -------------------------------------------------

# the round's digest as tests/test_looped_lm.py takes the olmo cell's
# and tests/test_keye_lm.py the ouro cell's (on the CPU the selected
# layers lower their masked dense form). Taken anew by PR 43, which
# changed this cell's program on purpose (the expert layers' work over
# row blocks, ops/routed_experts.py; until then the parent's of PR 41,
# ad653fe3...): it holds every later PR that does not mean to touch
# the softmax-routed, selected path
KEYE_ROUND_SHA256 = \
    "fcbea0abddb687ffec26fccf4e15803a9ad6ecc8d1612505f617a70bc7cd00fb"


def test_the_keye_cells_lowered_round_is_unchanged(tmp_path):
    """``keye_vl2_30b_a3b_l4.fedavg_k2_e10``'s round program at the
    cell's own flags and widths (nothing allocated: abstract state, a
    store of 4 rows a client), lowered on the CPU: the split of the
    block's two flags, the per-layer feed-forward, the router's new
    arguments and the parts stacked by key moved no operation of the
    softmax-routed, selected path."""
    from benchmark.harness import runner
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.cli import args_to_config, build_parser
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    cell = runner.load_cell("keye_vl2_30b_a3b_l4.fedavg_k2_e10")
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
    text = with_field_names(
        re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1", text), t)
    assert hashlib.sha256(text.encode()).hexdigest() == KEYE_ROUND_SHA256
