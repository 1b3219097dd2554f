"""The sparse-expert, sparse-attention model read from a file
(``models/hybrid_lm.py`` with ``ops/sparse_attention.py`` and
``ops/routed_experts.py``) against the plain reference
(``benchmark/reference/keye_vl2.py``) at a small specification: 2
layers, 4 query on 2 key/value heads of 16, 8 routed experts of which
4 are held, 2 a token, 8 selected keys on 24-token rows in query chunks
of 8. The shares of the experts add up to the whole layer; the
selection is a brute-force sort's; nothing is dropped; each loss part
trains its own leaves; the engine and the launcher run it."""
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.datagen import tokens as token_files
from benchmark.reference import _ops, keye_vl2 as reference
from fedtorch_tpu.models import hybrid_lm
from fedtorch_tpu.models.hybrid_lm import HybridLM, load_spec, param_shapes
from fedtorch_tpu.ops import routed_experts, sparse_attention
from test_sequential_round import (
    gauges_of, lm_cfg, round_rows, trainer_of, with_field_names,
)

SMALL = {
    "model_type": "KeyeVL2", "vocab_size": 64, "hidden_size": 32,
    "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-6, "rope_theta": 10000000,
    "rope_scaling": {"mrope_section": [2, 3, 3], "rope_type": "default",
                     "type": "default"},
    "num_experts": 4, "num_local_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 24, "norm_topk_prob": True,
    "decoder_sparse_step": 1, "mlp_only_layers": [],
    "first_expert_held": 2, "published": {"num_experts": 8},
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 4,
                  "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                  "q_chunk_size": 8, "topk": 8},
    "tie_word_embeddings": False, "attention_bias": False,
    "launcher": {"ignored": True},
}
CLIENTS = 6
INDEXER = ("index_q", "index_k", "index_w")


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


def is_indexer(path) -> bool:
    return path[-1].key in INDEXER


# -- against the reference ------------------------------------------------------

@pytest.mark.parametrize("remat", [True, False])
def test_loss_and_gradients_equal_the_reference(spec_file, remat):
    model = model_of(spec_file, remat=remat)
    params = model.init(jax.random.key(1))
    x = tokens((2, 24))
    spec = reference.load_spec(spec_file)
    with jax.default_matmul_precision("highest"):
        (loss, (acc, parts)), grads = loss_and_grads(model, params, x)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.make_loss(spec)(p, x, None)))(params)
        _, ce, index_loss = reference.objective(params, x, spec)
    assert 0.0 <= float(acc) <= 1.0
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(parts["ce"], ce, rtol=1e-6)
    np.testing.assert_allclose(parts["index_loss"], index_loss, rtol=1e-5)
    assert float(index_loss) > 0.01     # the term is not idle at the seed
    worst, gaps = worst_gap(grads, want_grads)
    assert worst < 1e-5, gaps
    assert all(float(jnp.max(jnp.abs(g))) > 0
               for g in jax.tree.leaves(want_grads))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bfloat16_operands_stay_inside_a_band(spec_file, seed):
    """bfloat16 operands move keys across the topk-th place and tokens
    across the router's top-2, so a few queries and tokens take other
    sets than the float32 model's. The band: the loss within 1e-3 of
    itself (read: 8e-6 to 4e-5), the whole gradient within a cosine of
    0.998 and 5 % in norm of the float32 one (read: 0.9995, 2.8 %),
    every leaf within 75 % of its largest entry (a flipped choice
    moves one expert's rows: 0.50 read on ``mlp.up``), parameters and
    gradients float32."""
    model = model_of(spec_file, dtype="bfloat16")
    params = model.init(jax.random.key(1 + seed))
    assert {x.dtype for x in jax.tree.leaves(params)} == {
        jnp.dtype("float32")}
    x = tokens((2, 24), seed=seed)
    (loss16, _), grads16 = loss_and_grads(model, params, x)
    (loss32, _), grads32 = loss_and_grads(model_of(spec_file), params, x)
    assert loss16.dtype == jnp.float32
    assert abs(float(loss16) - float(loss32)) < 1e-3 * float(loss32)
    flat = lambda g: jnp.concatenate([v.reshape(-1)
                                      for v in jax.tree.leaves(g)])
    a, b = flat(grads16), flat(grads32)
    assert float(a @ b / jnp.linalg.norm(a) / jnp.linalg.norm(b)) > 0.998
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 0.05
    worst, gaps = worst_gap(grads16, grads32)
    assert worst < 0.75, gaps
    assert all(g.dtype == jnp.float32 for g in jax.tree.leaves(grads16))


def test_remat_and_logits_agree_with_the_loss(spec_file):
    x = tokens((1, 24), seed=3)
    plain = model_of(spec_file, remat=False)
    params = plain.init(jax.random.key(2))
    loss, _, parts = jax.jit(plain.token_loss_parts)(params, x)
    loss_remat, _ = jax.jit(model_of(spec_file).token_loss)(params, x)
    np.testing.assert_allclose(loss, loss_remat, rtol=1e-6)
    logits = jax.jit(plain.apply)(params, x)
    assert logits.shape == (1, 24, SMALL["vocab_size"])
    logp = jax.nn.log_softmax(logits[:, :-1])
    want = -jnp.mean(jnp.take_along_axis(logp, x[:, 1:, None], axis=-1))
    # evaluation reads CE alone; the training loss adds L_I
    np.testing.assert_allclose(parts["ce"], want, rtol=1e-6)
    np.testing.assert_allclose(loss, want + parts["index_loss"], rtol=1e-6)


# -- the experts ------------------------------------------------------------------

def expert_case(seed=0, tokens_=40, routed=8, d=32, f=24):
    keys = jax.random.split(jax.random.key(seed), 5)
    u = jax.random.normal(keys[0], (tokens_, d))
    full = {"router": jax.random.normal(keys[1], (d, routed)),
            "gate": 0.3 * jax.random.normal(keys[2], (routed, d, f)),
            "up": 0.3 * jax.random.normal(keys[3], (routed, d, f)),
            "down": 0.3 * jax.random.normal(keys[4], (routed, f, d))}
    return u, full


def share_of(full, first, held):
    cut = lambda w: w[first:first + held]
    return {"router": full["router"], "gate": cut(full["gate"]),
            "up": cut(full["up"]), "down": cut(full["down"])}


def program_share(p, u, first, per_token=2):
    gates, chosen = routed_experts.route(u @ p["router"], per_token, True)
    return routed_experts.expert_share(p, u, gates, chosen, first=first,
                                       dt=jnp.float32)


def reference_share(p, u, first, routed=8, per_token=2):
    return reference.experts(p, u, {
        "num_experts_per_tok": per_token, "norm_topk_prob": True,
        "first_expert_held": first, "routed_experts": routed},
        _ops.identity)


@pytest.mark.parametrize("held", [1, 2, 4, 8])
def test_the_shares_add_up_to_the_whole_layer(held):
    """The guide's test of the cut: the parts of the expert layer's
    result that all the shares give (8 / ``held`` chips, the router
    counted on each and its probabilities normalised over all chosen
    experts), summed, are the uncut reference's layer."""
    u, full = expert_case()
    with jax.default_matmul_precision("highest"):
        whole = reference_share(full, u, 0)
        parts = [program_share(share_of(full, first, held), u, first)
                 for first in range(0, 8, held)]
        same = [reference_share(share_of(full, first, held), u, first)
                for first in range(0, 8, held)]
    np.testing.assert_allclose(sum(o for o, _ in parts), whole, atol=2e-5)
    for (o, _), r in zip(parts, same):
        np.testing.assert_allclose(o, r, atol=2e-5)
    # every token-expert pair is computed on exactly one share
    assert sum(float(c["pairs"]) for _, c in parts) == u.shape[0] * 2


def test_dropless_under_the_worst_imbalance():
    """A router biased so that every token picks held experts only, and
    among them one far more than the others: every pair is computed
    (the buffer's worst case), none clipped, and the result is the
    reference's."""
    u, full = expert_case(seed=1)
    bias = jnp.asarray([0, 0, 9.0, 6, 6, 6, 0, 0])
    full["router"] = 0.05 * full["router"]
    p = share_of(full, 2, 4)
    route = lambda u_: routed_experts.route(u_ @ p["router"] + bias, 2, True)
    gates, chosen = route(u)
    assert bool(jnp.all((chosen >= 2) & (chosen < 6)))
    with jax.default_matmul_precision("highest"):
        out, counters = routed_experts.expert_share(
            p, u, gates, chosen, first=2, dt=jnp.float32)
        want = sum(
            jnp.sum(jnp.where(chosen == 2 + j, gates, 0.0), -1)[:, None]
            * ((jax.nn.silu(u @ p["gate"][j]) * (u @ p["up"][j]))
               @ p["down"][j]) for j in range(4))
    assert float(counters["pairs"]) == u.shape[0] * 2
    # expert 2 is every token's first choice: 40 of the 80 pairs
    assert float(counters["load_max_over_mean"]) == 40 / 20
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_none_routed_here_gives_zero_and_finite_gradients():
    u, full = expert_case(seed=2)
    bias = jnp.asarray([9.0, 9, 0, 0, 0, 0, 0, 0])
    p = share_of(full, 4, 4)

    def f(p, u):
        gates, chosen = routed_experts.route(
            0.01 * (u @ p["router"]) + bias, 2, True)
        out, counters = routed_experts.expert_share(
            p, u, gates, chosen, first=4, dt=jnp.float32)
        return jnp.sum(out * out) + jnp.sum(out), (out, counters)

    (_, (out, counters)), grads = jax.value_and_grad(
        f, argnums=(0, 1), has_aux=True)(p, u)
    assert float(counters["pairs"]) == 0.0
    assert float(counters["load_max_over_mean"]) == 0.0
    assert float(jnp.max(jnp.abs(out))) == 0.0
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))


def test_the_plan_is_a_stable_sort_by_expert():
    chosen = jnp.asarray(np.random.RandomState(0).randint(0, 32, (200, 4)),
                         jnp.int32)
    pl = routed_experts.plan(chosen, 8, 6)
    local = np.asarray(chosen) - 8
    key = np.where((local >= 0) & (local < 6), local, 6).reshape(-1)
    order = np.argsort(key, kind="stable")
    np.testing.assert_array_equal(pl.order, order)
    np.testing.assert_array_equal(pl.slot, np.argsort(order))
    np.testing.assert_array_equal(pl.sizes, np.bincount(key, minlength=7)[:6])
    assert int(jnp.sum(pl.live)) == int(np.sum(key < 6))


# -- the selection ----------------------------------------------------------------

def attention_case(T=24, seed=0, B=2, H=4, KV=2, hd=16, J=4, di=8):
    k = jax.random.split(jax.random.key(seed), 6)
    return (jax.random.normal(k[0], (B, T, H, hd)),
            jax.random.normal(k[1], (B, T, KV, hd)),
            jax.random.normal(k[2], (B, T, KV, hd)),
            jax.random.normal(k[3], (B, T, J, di)),
            jax.random.normal(k[4], (B, T, di)),
            jax.random.normal(k[5], (B, T, J)))


def brute_force(q, k, v, qi, ki, wi, topk):
    """Every query on its own: sort its scores, take the best."""
    B, T, H, hd = q.shape
    group = H // k.shape[2]
    out = np.zeros((B, T, H, hd), np.float32)
    chosen = np.zeros((B, T, T), bool)
    q, k, v, qi, ki, wi = (np.asarray(t, np.float64)
                           for t in (q, k, v, qi, ki, wi))
    for b in range(B):
        for t in range(T):
            score = np.einsum("j,js->s", wi[b, t], np.maximum(
                qi[b, t] @ ki[b, :t + 1].T, 0.0))
            # the topk-th best score is the bar (scores are exactly 0
            # where no indexer head fires: equal ones all pass)
            bar = np.sort(score)[::-1][min(topk, t + 1) - 1]
            best = np.flatnonzero(score >= bar)
            chosen[b, t, best] = True
            for h in range(H):
                s = k[b, best, h // group] @ q[b, t, h] / np.sqrt(hd)
                p = np.exp(s - s.max())
                out[b, t, h] = (p / p.sum()) @ v[b, best, h // group]
    return out, chosen


@pytest.mark.parametrize("T,topk,chunk", [(24, 8, 8), (24, 8, 24),
                                          (16, 4, 2), (24, 24, 8)])
def test_the_selection_is_a_brute_force_sorts_and_causal(T, topk, chunk):
    case = attention_case(T)
    with jax.default_matmul_precision("highest"):
        out, _ = sparse_attention.selected_attention(
            *case, topk=topk, chunk=chunk, dt=jnp.float32)
        scores = sparse_attention.index_scores(case[3], case[4], case[5])
        mask = sparse_attention.select(scores, jnp.arange(T), topk)
    want, chosen = brute_force(*case, topk)
    np.testing.assert_array_equal(mask, chosen)
    assert not bool(jnp.any(jnp.triu(mask, 1)))       # causal
    assert bool(jnp.all(jnp.sum(mask, -1)
                        >= jnp.minimum(jnp.arange(T) + 1, topk)))
    assert int(jnp.sum(mask)) <= 2 * sparse_attention.selected_pairs(
        T, topk) + 4                                  # B = 2, few ties
    np.testing.assert_allclose(out, want, atol=2e-5)


def test_selecting_every_key_is_plain_grouped_head_causal_attention():
    """``topk >= T``: the layer equals causal softmax attention with
    each key head serving its group of query heads, and the indexer's
    term still reads the attention's probabilities."""
    q, k, v, qi, ki, wi = attention_case(16)
    with jax.default_matmul_precision("highest"):
        out, index_loss = sparse_attention.selected_attention(
            q, k, v, qi, ki, wi, topk=64, chunk=8, dt=jnp.float32)
        kk, vv = (jnp.repeat(t, 2, axis=2) for t in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kk) / 4.0
        p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((16, 16), bool)),
                                     s, -jnp.inf), axis=-1)
        want = jnp.einsum("bhqk,bkhd->bqhd", p, vv)
    np.testing.assert_allclose(out, want, atol=2e-5)
    assert float(index_loss) > 0.0


@pytest.mark.parametrize("chunk,topk,other_chunk", [
    (2, 8, 8), (4, 8, 8), (12, 12, 6), (24, 24, 8)])
def test_the_result_does_not_depend_on_the_query_chunk(tmp_path, chunk,
                                                       topk, other_chunk):
    """Chunks (and with them the bands of keys) are the tiling, not the
    mathematics: loss and gradients at chunks of 2, 4, 12 and 24 rows
    are those at another chunk that divides the same ``topk``."""
    x = tokens((2, 24), seed=5)
    sa = dict(SMALL["sa_config"], q_chunk_size=chunk, topk=topk)
    other = model_of(write_spec(tmp_path, "c.json", sa_config=sa))
    same = model_of(write_spec(tmp_path, "b.json", sa_config=dict(
        sa, q_chunk_size=other_chunk)))
    params = same.init(jax.random.key(4))
    with jax.default_matmul_precision("highest"):
        (loss, _), grads = loss_and_grads(same, params, x)
        (got, _), got_grads = loss_and_grads(other, params, x)
    np.testing.assert_allclose(got, loss, rtol=1e-6)
    worst, gaps = worst_gap(got_grads, grads)
    assert worst < 1e-5, gaps


def test_kth_largest_is_exact():
    rng = np.random.RandomState(3)
    x = rng.standard_normal((5, 7, 64)).astype(np.float32)
    x[0, 0, :40] = -np.inf            # fewer than k values in sight
    x[1, 1, :] = 0.0                  # all equal
    x[2, 2, 5] = x[2, 2, 9]           # a tie
    for k in (1, 8, 33, 64):
        got = sparse_attention.kth_largest(jnp.asarray(x), k)
        np.testing.assert_array_equal(got, np.sort(x, axis=-1)[..., -k])


def test_the_counters_from_shapes():
    assert sparse_attention.selected_pairs(8192, 2048) == 14681088
    assert sparse_attention.selected_pairs(24, 8) == 36 + 16 * 8
    assert sparse_attention.selected_share(2048, 2048) == 1.0
    assert round(sparse_attention.selected_share(8192, 2048), 4) == 0.4375
    with pytest.raises(ValueError, match="no whole number"):
        sparse_attention.chunk_of(20, 8)


# -- each loss part trains its own leaves ---------------------------------------

def test_each_loss_part_reaches_its_own_leaves(spec_file):
    """``L_I``'s gradient is zero on every leaf but the indexer's three
    a layer, ``CE``'s is zero on those, and after one SGD step of the
    sum no leaf is where it was."""
    model = model_of(spec_file)
    params = model.init(jax.random.key(6))
    x = tokens((2, 24), seed=7)
    part = lambda name: jax.jit(jax.grad(
        lambda p: model.token_loss_parts(p, x)[2][name]))(params)
    for name, own in (("index_loss", True), ("ce", False)):
        for path, g in jax.tree_util.tree_leaves_with_path(part(name)):
            moved = float(jnp.max(jnp.abs(g))) > 0
            assert moved == (is_indexer(path) == own), (name, path)
    (_, _), grads = loss_and_grads(model, params, x)
    stepped = jax.tree.map(lambda p, g: p - 0.02 * g, params, grads)
    frozen = [path for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(params),
        jax.tree.leaves(stepped)) if bool(jnp.all(a == b))]
    assert frozen == []


# -- the specification ------------------------------------------------------------

def test_the_benchmarks_configuration_counts_its_parameters():
    """The published widths with the cut the file states: counted from
    shapes, nothing allocated."""
    path = "benchmark/configs/keye_vl2_30b_a3b_l4.json"
    spec = load_spec(path)
    with open(path) as f:
        doc = json.load(f)
    assert spec.layer_types == ("full_attention",) * 4 and spec.prenorm
    assert (spec.num_attention_heads, spec.kv_heads, spec.head_size) == (
        32, 4, 128)
    assert spec.experts == hybrid_lm.Experts(128, 16, 0, 8, 768, True)
    assert spec.selection == hybrid_lm.Selection(16, 64, 2048, 512)
    assert (doc["published"]["num_experts"], doc["num_experts"],
            doc["published"]["vocab_size"], doc["vocab_size"],
            doc["published"]["num_hidden_layers"]) == (
        128, 16, 151936, 18992, 48)
    shapes = param_shapes(spec)
    count = lambda t: sum(int(np.prod(s)) for s in jax.tree.leaves(
        t, is_leaf=lambda s: isinstance(s, tuple)))
    mixer = shapes["layer_0"]["mixer"]
    assert count({k: mixer[k] for k in INDEXER}) == 2260992
    assert shapes["layer_0"]["mlp"]["gate"] == (16, 2048, 768)
    assert shapes["layer_0"]["mlp"]["down"] == (16, 768, 2048)
    assert count(shapes["layer_0"]) == doc["parameters"]["layer"] \
        == 96899328
    assert count(shapes) == doc["parameters"]["total"] == 465390592
    # under the budget's names: the products a layer's checkpoint may keep
    assert set(hybrid_lm.layer_products(spec, "full_attention")) == {
        "mixer.q", "mixer.k", "mixer.v", "mixer.o", "mixer.index_q",
        "mixer.index_k", "mixer.index_w", "mlp.router", "mlp.gate",
        "mlp.up", "mlp.down"}


def test_a_file_without_the_mechanisms_runs_the_same_block(tmp_path):
    """A layer is an expert layer because the file's keys say so, and
    selected because ``sa_config`` is there: without them the pre-norm
    block has a dense feed-forward and plain grouped-head attention, and
    a file with ``sa_config`` and rows no longer than ``topk`` selects
    every key."""
    plain = model_of(write_spec(tmp_path, "p.json", sa_config=...,
                                num_experts=0))
    assert plain.spec.experts is None and plain.spec.selection is None
    assert not plain.gauge_names
    assert "lm_selected_share" not in plain.trace_gauges(1, 24)
    shapes = param_shapes(plain.spec)["layer_0"]
    assert shapes["mlp"] == {"gate": (32, 96), "up": (32, 96),
                             "down": (96, 32)}
    assert set(shapes["mixer"]) == {"wq", "wk", "wv", "wo", "q_norm",
                                    "k_norm"}
    x = tokens((1, 24), seed=8)
    loss, _, parts = jax.jit(plain.token_loss_parts)(
        plain.init(jax.random.key(0)), x)
    assert parts == {} and np.isfinite(float(loss))
    # sa_config, rows no longer than topk: every causal key is selected
    wide = model_of(write_spec(tmp_path, "w.json", sa_config=dict(
        SMALL["sa_config"], topk=32)))
    params = wide.init(jax.random.key(0))
    assert wide.trace_gauges(1, 24)["lm_selected_share"] == 1.0
    with jax.default_matmul_precision("highest"):
        got = jax.jit(wide.apply)(params, x)
        # the same weights without the indexer: plain attention
        bare = jax.tree_util.tree_map_with_path(
            lambda path, v: None if is_indexer(path) else v, params)
        want = jax.jit(model_of(write_spec(
            tmp_path, "n.json", sa_config=...)).apply)(bare, x)
    np.testing.assert_allclose(got, want, atol=2e-5)


@pytest.mark.parametrize("change,match", [
    ({"tie_word_embeddings": True}, "tied embeddings"),
    ({"attention_bias": True}, "attention biases"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4.0}},
     "rope_scaling"),
    ({"rope_scaling": {"mrope_section": [2, 3, 4], "rope_type": "default"}},
     "rope_scaling"),
    ({"mlp_only_layers": [0]}, "mlp_only_layers"),
    ({"decoder_sparse_step": 2}, "decoder_sparse_step"),
    ({"sa_config": dict(SMALL["sa_config"], topk=12)},
     "topk 12 is no multiple of q_chunk_size 8"),
    ({"sa_config": dict(SMALL["sa_config"], indexer_num_kv_heads=2)},
     "one key head"),
    ({"sa_config": {"topk": 8}}, "sa_config lacks"),
    ({"moe_intermediate_size": ...}, "lacks.*moe_intermediate_size"),
    ({"first_expert_held": 6}, "do not lie within the router's 8"),
    ({"model_type": "olmo_hybrid"}, "written for the pre-norm block"),
    ({"embedding_init_std": 0.0}, "embedding_init_std"),
])
def test_specification_refusals_by_name(tmp_path, change, match):
    with pytest.raises(ValueError, match=match):
        load_spec(write_spec(tmp_path, "bad.json", **change))


@pytest.mark.parametrize("std", [None, 1.0])
def test_the_seeded_embedding_takes_the_files_size(tmp_path, std):
    """``embedding_init_std`` sizes the embedding's rows and nothing
    else; a file without it gets every matrix's 0.02, as the accepted
    cells' files do."""
    wide = write_spec(tmp_path, vocab_size=4096,
                      embedding_init_std=... if std is None else std)
    assert load_spec(wide).embedding_init_std == (std or 0.02)
    params = model_of(wide).init(jax.random.key(5))
    assert float(jnp.std(params["embed"])) == pytest.approx(
        std or 0.02, rel=0.02)
    assert float(jnp.std(params["head"])) == pytest.approx(0.02, rel=0.02)
    rest = model_of(write_spec(tmp_path, "r.json", vocab_size=4096)).init(
        jax.random.key(5))
    for name in ("head", "final_norm", "layer_1"):
        jax.tree.map(np.testing.assert_array_equal, params[name],
                     rest[name])


# -- in the engine ----------------------------------------------------------------

@pytest.fixture(scope="module")
def files(tmp_path_factory):
    root = tmp_path_factory.mktemp("keye")
    data = token_files.write(str(root / "data"), 7, clients=CLIENTS,
                             rows_per_client=5, seq_len=24,
                             vocab_size=SMALL["vocab_size"], test_rows=3)
    return {"spec": write_spec(root), "data": data}


def test_sequential_round_equals_the_vmapped_round(files):
    """Same cohort, rows, keys and weights for the sparse model: the
    server's parameters agree to float32 rounding of the sum's order;
    the sequential round also reports the routing and indexer gauges,
    through the round's one scalar fetch."""
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
    assert "lm_exit_entropy" not in g
    # 24 tokens x 2 a token x 4 of 8 experts held: 24 pairs expected
    assert 10 < float(g["lm_moe_pairs_local"]) < 40
    assert 1.0 <= float(g["lm_moe_load_max_over_mean"]) <= 4.0
    assert 0.0 < float(g["lm_index_loss"]) < 2.0
    gauges = ts.telemetry_gauges()
    assert gauges["tokens_trained"] == 3 * 2 * 1 * 24
    assert gauges["lm_selected_share"] == (36 + 16 * 8) / 300
    assert "ut_steps" not in gauges
    scalars = ts.round_host_scalars(clients, ms)
    assert scalars["lm_index_loss"] == float(g["lm_index_loss"])
    assert scalars["lm_moe_pairs_local"] == float(g["lm_moe_pairs_local"])


def test_launcher_rounds_evaluation_save_and_resume(files, tmp_path):
    from fedtorch_tpu.cli import run_experiment
    run_dir = str(tmp_path / "run")
    result = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir))
    assert 0.0 <= result["test_top1"] <= 1.0
    rows = round_rows(run_dir)
    assert [r["round"] for r in rows] == [0, 1]
    assert all(np.isfinite(r["loss"]) and "eval_s" in r
               and r["tokens_trained"] == 3 * 2 * 24
               and r["lm_selected_share"] == (36 + 16 * 8) / 300
               and 0 < r["lm_moe_pairs_local"] < 48
               # under a row tile the block is the buffer: one trip
               and r["lm_moe_rows_visited"] == 48
               and r["lm_moe_load_max_over_mean"] >= 1.0
               and 0 < r["lm_index_loss"] < 2.0
               and r["dropped"] == 0 for r in rows)
    again = run_experiment(lm_cfg(files, "sequential", run_dir=run_dir,
                                  num_comms=3, resume=run_dir))
    assert [r["round"] for r in round_rows(run_dir)] == [0, 1, 2]
    assert 0.0 <= again["test_top1"] <= 1.0


# -- the language cells' programs ---------------------------------------------

# the round's digest as tests/test_looped_lm.py takes the olmo cell's
# (the text with the counters off its private functions' names), with
# what the chooser keeps where the backend reports no memory (all): the
# parent's of PR 39 (d6a37a4), whose shared model file this PR widened
OURO_ROUND_SHA256 = \
    "1916f9d9f901753ad26e6efcdf4ad6757596e499d2af6b77654ba52006d2bd55"


def test_the_ouro_cells_lowered_round_is_unchanged(tmp_path):
    """``ouro_2_6b_l8.fedavg_k2_e10``'s round program at the cell's own
    flags and widths (nothing allocated: abstract state, a store of 4
    rows a client), lowered on the CPU: grouped heads, a free head
    size, the pre-norm block, experts and the selection moved no
    operation of the looped path (the olmo cell's digests stand in
    tests/test_looped_lm.py)."""
    from benchmark.harness import runner
    from fedtorch_tpu.algorithms import make_algorithm
    from fedtorch_tpu.cli import args_to_config, build_parser
    from fedtorch_tpu.data import build_federated_data
    from fedtorch_tpu.models import define_model
    from fedtorch_tpu.parallel import FederatedTrainer

    cell = runner.load_cell("ouro_2_6b_l8.fedavg_k2_e10")
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
    assert hashlib.sha256(text.encode()).hexdigest() == OURO_ROUND_SHA256


def test_rows_past_the_last_group_move_no_number():
    """The grouped products run over the groups' rows and no more:
    whatever the buffer's idle rows hold (here large numbers where
    ``expert_share`` puts zeros), the groups' rows of the result, the
    weights' cotangent and the groups' rows of the operand's cotangent
    are those of each group's own dense product."""
    rng = np.random.RandomState(3)
    m, d, f = 24, 16, 8
    sizes = jnp.asarray([5, 0, 9, 3], jnp.int32)
    used = int(sizes.sum())
    x = jnp.asarray(rng.randn(m, d), jnp.float32)
    w = jnp.asarray(rng.randn(4, d, f), jnp.float32)
    c = jnp.asarray(rng.randn(m, f), jnp.float32)
    live = (jnp.arange(m) < used)[:, None]

    def loss(x, w):
        out = routed_experts.grouped_dot(x, w, sizes)
        return jnp.sum(jnp.where(live, out * c, 0.0)), out

    def dense(x, w):
        ends = np.cumsum(sizes)
        out = jnp.concatenate(
            [x[e - n:e] @ w[g] for g, (e, n) in enumerate(zip(ends, sizes))])
        return jnp.sum(out * c[:used]), out

    run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
    (_, want), (want_dx, want_dw) = jax.value_and_grad(
        dense, argnums=(0, 1), has_aux=True)(x, w)
    for idle in (0.0, 1e6):
        filled = jnp.where(live, x, idle)
        (_, got), (dx, dw) = run(filled, w)
        np.testing.assert_allclose(got[:used], want, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(dx[:used], want_dx[:used], rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(dw, want_dw, rtol=1e-5, atol=1e-5)
