"""The fused kernels of the attention over selected keys
(``ops/pallas/selected_attention.py``) against the masked dense form
(``ops/sparse_attention.py``: ``_chunk``), in the Pallas interpreter on
the CPU: the kernels' own results, the layer's results and gradients
through the one backward rule that calls them, and the whole model
against the plain reference under ``remat`` (the layer's policy keeps
the backward rule's residuals under one name: a kept threshold beside
scores made again by another compiled pass gave wrong gradients in
silence, ``ops/sparse_attention.py``)."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import keye_vl2 as reference
from fedtorch_tpu.ops import attention_dispatch, sparse_attention
from fedtorch_tpu.ops.pallas import selected_attention as kernels
from test_keye_lm import (
    loss_and_grads, model_of, tokens, worst_gap, write_spec,
)

TILES = (8, 8)


@pytest.fixture
def fused(monkeypatch):
    """The layer takes the kernels whatever the backend, on tiles the
    small shapes divide; the interpreter runs them."""
    monkeypatch.setattr(sparse_attention, "takes_kernel", lambda *a: True)
    monkeypatch.setattr(kernels, "tiles", lambda *a: TILES)


def chunk_case(B=2, C=16, S=32, H=8, KV=1, hd=16, first=16, seed=0):
    """A chunk's operands: ``first`` the first query's position."""
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return (f(B, C, H, hd), f(B, S, KV, hd), f(B, S, KV, hd), f(B, C, S),
            first + jnp.arange(C))


def dense_chunk(q, k, v, scores, rows, topk, dt):
    """The masked dense form of a chunk: (``o``, the log-sum-exp
    [B, H, C], the heads' summed probabilities)."""
    B, C, H, hd = q.shape
    KV = k.shape[2]
    sel = sparse_attention.select(scores, rows, topk)
    s = jnp.einsum("bckgd,bskd->bkgcs",
                   q.astype(dt).reshape(B, C, KV, H // KV, hd),
                   k.astype(dt), preferred_element_type=jnp.float32) \
        / math.sqrt(hd)
    s = jnp.where(sel[:, None, None], s, -jnp.inf)
    probs = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgcs,bskd->bckgd", probs.astype(dt), v.astype(dt),
                   preferred_element_type=jnp.float32)
    return (o.reshape(B, C, H, hd),
            jax.nn.logsumexp(s, axis=-1).reshape(B, H, C),
            jnp.sum(probs, axis=(1, 2)) / H)


def kernel_chunk(q, k, v, scores, rows, topk, dt):
    kth = sparse_attention.threshold(scores, rows, topk)
    o, lse = kernels.forward(q, k, v, scores, kth, rows, dt,
                             tile_q=TILES[0], tile_k=TILES[1])
    return o, lse, kernels.summed_probabilities(
        q, k, lse, scores, kth, rows, dt, tile_q=TILES[0], tile_k=TILES[1])


def rel(got, want):
    return float(jnp.max(jnp.abs(got - want))
                 / (jnp.max(jnp.abs(want)) + 1e-12))


CHUNKS = {
    "eight_heads_a_key_head": dict(H=8, KV=1),
    "one_head_a_key_head": dict(H=2, KV=2),
    "groups_of_two": dict(H=4, KV=2),
    "rows_with_fewer_keys_than_topk": dict(first=0, S=16),
    "a_band_longer_than_its_chunk_sees": dict(first=8, S=48),
}


@pytest.mark.parametrize("dt,tol", [("float32", 2e-6), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("name", CHUNKS)
@pytest.mark.parametrize("topk", [8, 64])
def test_a_chunk_equals_the_masked_dense_form(name, topk, dt, tol):
    """``o``, the log-sum-exp and the target of one chunk, the
    selection dropping keys (``topk`` 8) and taking every causal one
    (64, no fewer than the keys)."""
    case, dt = chunk_case(**CHUNKS[name]), jnp.dtype(dt)
    want = dense_chunk(*case, topk, dt)
    got = kernel_chunk(*case, topk, dt)
    for g, w in zip(got, want):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        assert rel(g, w) < tol
    # of mass 1 a row, and nothing outside the selection
    np.testing.assert_allclose(jnp.sum(got[2], axis=-1), 1.0, rtol=1e-5)
    sel = sparse_attention.select(case[3], case[4], topk)
    assert float(jnp.max(jnp.where(sel, 0.0, got[2]))) == 0.0


@pytest.mark.parametrize("name", CHUNKS)
def test_the_backward_kernel_equals_the_dense_forms_gradients(name):
    case = chunk_case(**CHUNKS[name])
    q, k, v, scores, rows = case
    do = jnp.asarray(np.random.RandomState(7).randn(*q.shape), jnp.float32)
    o, pull = jax.vjp(lambda q, k, v: dense_chunk(
        q, k, v, scores, rows, 8, jnp.float32)[0], q, k, v)
    kth = sparse_attention.threshold(scores, rows, 8)
    _, lse = kernels.forward(q, k, v, scores, kth, rows, jnp.float32,
                             tile_q=TILES[0], tile_k=TILES[1])
    got = kernels.backward(q, k, v, scores, kth, rows, lse, o, do,
                           jnp.float32, tile_q=TILES[0], tile_k=TILES[1])
    for g, w in zip(got, pull(do)):
        assert g.dtype == jnp.float32 and rel(g, w) < 2e-6


def test_scores_that_tie_with_the_threshold_are_all_taken():
    """Indexer scores from a handful of values: the ``topk``-th place
    is shared, and the kernels' mask takes every tie as ``select``
    does."""
    q, k, v, _, rows = chunk_case(H=4, KV=2)
    scores = jnp.asarray(np.random.RandomState(3).randint(
        0, 4, (2, 16, 32)), jnp.float32)
    sel = sparse_attention.select(scores, rows, 8)
    assert int(jnp.max(jnp.sum(sel, axis=-1))) > 8       # ties are there
    want = dense_chunk(q, k, v, scores, rows, 8, jnp.float32)
    got = kernel_chunk(q, k, v, scores, rows, 8, jnp.float32)
    for g, w in zip(got, want):
        assert rel(g, w) < 2e-6
    assert bool(jnp.all((got[2] > 0) == sel))


def test_the_shapes_that_tile():
    # the cell's: 32 on 4 heads of 128, chunks of 512, the bands' keys
    assert [kernels.tiles(32, 4, 128, 512, S)
            for S in (1024, 2048, 3072, 4096)] == [(512, 512)] * 4
    assert kernels.tiles(32, 4, 128, 512, 1280) == (512, 256)
    # a group of 32 heads: no more than 4096 rows a score tile
    assert kernels.tiles(32, 1, 128, 512, 4096) == (128, 512)
    # the model tests': a head of 16, a chunk of 8
    assert kernels.tiles(4, 2, 16, 8, 24) is None
    assert kernels.tiles(32, 4, 128, 500, 4096) is None
    assert kernels.tiles(30, 4, 128, 512, 4096) is None
    assert kernels.tiles(32, 4, 128, 512, 4000) is None
    # the CPU never takes them, whatever the shapes
    assert not attention_dispatch.on_tpu()
    assert not sparse_attention.takes_kernel(32, 4, 128, 512, 4096)


# -- the layer: one backward rule --------------------------------------------

def layer_case(T=32, seed=0, B=2, H=4, KV=2, hd=16, J=4, di=8):
    r = np.random.RandomState(seed)
    f = lambda *s: jnp.asarray(r.randn(*s), jnp.float32)
    return (f(B, T, H, hd), f(B, T, KV, hd), f(B, T, KV, hd),
            f(B, T, J, di), f(B, T, di), f(B, T, J))


def layer(case, topk, chunk, dt, wrap=lambda f: f):
    """(``o``, ``L_I``, the gradients of a loss that reads both) of
    ``selected_attention`` as the fixture has it."""
    w = jnp.asarray(np.random.RandomState(9).randn(*case[0].shape),
                    jnp.float32)

    def loss(*a):
        o, index_loss = wrap(lambda *a: sparse_attention.selected_attention(
            *a, topk=topk, chunk=chunk, dt=dt))(*a)
        return jnp.sum(o * w) + 3.0 * index_loss, (o, index_loss)

    (_, (o, index_loss)), grads = jax.jit(jax.value_and_grad(
        loss, argnums=range(6), has_aux=True))(*case)
    return o, index_loss, grads


LAYERS = {
    # T, topk, chunk
    "two_bands_of_two_chunks": (32, 8, 8),
    "one_chunk": (32, 8, 32),
    "four_bands_of_different_key_lengths": (64, 8, 8),
    "no_more_keys_than_topk": (32, 64, 8),
    "three_chunks_one_band": (24, 8, 8),
}


@pytest.mark.parametrize("dt,tol", [("float32", 5e-6), ("bfloat16", 3e-2)])
@pytest.mark.parametrize("name", LAYERS)
def test_the_layer_equals_the_masked_dense_form(monkeypatch, name, dt, tol):
    """``o``, ``L_I`` and the gradients of ``q``, ``k``, ``v``, ``qi``,
    ``ki``, ``wi``: the fused form's one backward rule against JAX's own
    derivative of the dense chunks."""
    T, topk, chunk = LAYERS[name]
    case = layer_case(T)
    want = layer(case, topk, chunk, jnp.dtype(dt))
    monkeypatch.setattr(sparse_attention, "takes_kernel", lambda *a: True)
    monkeypatch.setattr(kernels, "tiles", lambda *a: TILES)
    got = layer(case, topk, chunk, jnp.dtype(dt))
    assert rel(got[0], want[0]) < tol
    np.testing.assert_allclose(got[1], want[1], rtol=max(tol, 1e-5))
    for g, w in zip(got[2], want[2]):
        assert g.dtype == jnp.float32 and g.shape == w.shape
        assert rel(g, w) < tol


def test_under_a_checkpoint_with_the_layers_policy(fused):
    """A rematerialized layer keeps what the backward rule reads of the
    forward pass (one name), or nothing: the gradients are the plain
    ones."""
    case = layer_case()
    policy = jax.checkpoint_policies.save_only_these_names(
        *sparse_attention.KEPT[1:])
    want = layer(case, 8, 8, jnp.float32)
    got = layer(case, 8, 8, jnp.float32,
                wrap=lambda f: jax.checkpoint(f, policy=policy))
    bare = layer(case, 8, 8, jnp.float32, wrap=jax.checkpoint)
    for other in (got, bare):
        np.testing.assert_allclose(other[1], want[1], rtol=1e-6)
        for g, w in zip(other[2], want[2]):
            assert rel(g, w) < 1e-6


def test_under_vmap_over_clients(fused):
    """The cohort's clients as a mapped leading axis (the vmapped
    round): each client's results are its own call's."""
    cases = [layer_case(seed=s, B=1) for s in (0, 1, 2)]
    stacked = tuple(jnp.stack(t) for t in zip(*cases))

    def loss(*a):
        o, index_loss = sparse_attention.selected_attention(
            *a, topk=8, chunk=8, dt=jnp.float32)
        return jnp.sum(o * o) + index_loss

    got = jax.jit(jax.vmap(jax.value_and_grad(loss, argnums=range(6))))(
        *stacked)
    for n, case in enumerate(cases):
        want = jax.jit(jax.value_and_grad(loss, argnums=range(6)))(*case)
        np.testing.assert_allclose(got[0][n], want[0], rtol=1e-6)
        for g, w in zip(got[1], want[1]):
            assert rel(g[n], w) < 1e-6


@pytest.mark.parametrize("remat", [True, False])
def test_the_model_equals_the_reference_through_the_kernels(
        fused, tmp_path, remat):
    """The whole model's loss and gradients against the plain
    reference with every selected layer on the kernels, with and
    without ``remat`` (thresholds and scores of one pass:
    ``ops/sparse_attention.py``)."""
    spec_file = write_spec(tmp_path)
    model = model_of(spec_file, remat=remat)
    params = model.init(jax.random.key(1))
    x = tokens((2, 24))
    spec = reference.load_spec(spec_file)
    with jax.default_matmul_precision("highest"):
        (loss, (_, parts)), grads = loss_and_grads(model, params, x)
        want, want_grads = jax.jit(jax.value_and_grad(
            lambda p: reference.make_loss(spec)(p, x, None)))(params)
        _, _, index_loss = reference.objective(params, x, spec)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(parts["index_loss"], index_loss, rtol=1e-5)
    worst, gaps = worst_gap(grads, want_grads)
    assert worst < 1e-5, gaps
