"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence written out token by token
(``benchmark/reference/olmo_hybrid.py:recurrent_delta_rule``): values
and gradients, with the decay near 0 and near 1, ``beta`` near 2, one
key repeated through the sequence, lengths that are and are not a
multiple of the chunk, and the chunk at 16 and at the model's 64. The
chunk's inverse (``unit_lower_inverse``) also on its own, against
``numpy.linalg.inv`` in float64, and its backward rule against JAX's
derivative of ``jnp.linalg.inv``."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import recurrent_delta_rule
from fedtorch_tpu.ops.delta_rule import (
    chunk_gated_delta_rule, unit_lower_inverse,
)

B, H, DK, DV, CHUNK = 2, 3, 8, 12, 16

NEAR_1_NEAR_2 = lambda r, s: (-1e-4 * r.rand(*s), np.full(s, 1.99))
REGIMES = {
    # log decay, beta
    "mixed": lambda r, s: (-0.5 * r.rand(*s), 2.0 * r.rand(*s)),
    "decay_near_0": lambda r, s: (-6.0 - r.rand(*s), 1.0 + r.rand(*s)),
    "decay_near_1_beta_near_2": NEAR_1_NEAR_2,
    # every token of a head brings the same key: the chunk's system
    # is 1.99 times the decays below the diagonal, the case in which
    # the powers of its strict part grow like binomials
    "repeated_key_beta_near_2": NEAR_1_NEAR_2,
}


def inputs(regime, T, seed=0):
    r = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(B, T, H, DK)) / np.sqrt(DK)
    k = unit(r.randn(B, T, H, DK))
    if regime.startswith("repeated_key"):
        k = np.broadcast_to(k[:, :1], k.shape)
    v = r.randn(B, T, H, DV)
    g, beta = REGIMES[regime](r, (B, T, H))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("T", [64, 100, 13])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_chunked_equals_recurrent_values_and_gradients(regime, T, chunk):
    args = inputs(regime, T)
    chunked = lambda *a: chunk_gated_delta_rule(*a, chunk=chunk)
    recurrent = lambda *a: recurrent_delta_rule(*a, block=CHUNK)
    with jax.default_matmul_precision("highest"):
        both = lambda f: jax.jit(lambda *a: (f(*a), jax.grad(
            lambda *b: jnp.sum(jnp.sin(f(*b))), argnums=range(5))(*a)))
        (o_c, g_c), (o_r, g_r) = both(chunked)(*args), \
            both(recurrent)(*args)
    assert o_c.shape == (B, T, H, DV)
    scale = float(jnp.max(jnp.abs(o_r)))
    np.testing.assert_allclose(o_c, o_r, atol=2e-5 * max(scale, 1.0))
    # one scale for all five: where the decay is near 0 its own
    # gradient is of the order of the decay itself
    top = max(float(jnp.max(jnp.abs(g))) for g in g_r)
    for name, a, b in zip("q k v g beta".split(), g_c, g_r):
        np.testing.assert_allclose(a, b, atol=2e-5 * top, err_msg=name)


def test_padding_leaves_the_kept_outputs_alone():
    """A length that is no multiple of the chunk reads as the head of
    a longer sequence: causal, and padded tokens leave no trace."""
    args = inputs("mixed", 48)
    whole = chunk_gated_delta_rule(*args, chunk=CHUNK)
    head = chunk_gated_delta_rule(*(a[:, :37] for a in args), chunk=CHUNK)
    np.testing.assert_allclose(head, whole[:, :37], atol=1e-6)


def test_bfloat16_operands_accumulate_in_float32():
    q, k, v, g, beta = inputs("mixed", 64)
    bf = lambda x: x.astype(jnp.bfloat16)
    o = chunk_gated_delta_rule(bf(q), bf(k), bf(v), g, beta, chunk=CHUNK)
    ref = recurrent_delta_rule(q, k, v, g, beta, block=CHUNK)
    assert o.dtype == jnp.float32
    rel = float(jnp.linalg.norm(o - ref) / jnp.linalg.norm(ref))
    assert rel < 0.03, rel


def strictly_lower(kind, n, batch=(2, 3)):
    if kind == "all_1.99":
        l = np.full(batch + (n, n), 1.99)
    else:
        l = np.random.RandomState(n).randn(*batch, n, n)
    return np.tril(l, -1)


@pytest.mark.parametrize("n", [8, 16, 24, 64])
@pytest.mark.parametrize("kind", ["random", "all_1.99"])
def test_unit_lower_inverse_equals_float64_inverse(kind, n):
    """With every entry 1.99 (one key repeated under beta near 2) the
    inverse's entries stay under 2 while the powers of ``l`` pass 1e18
    at n = 64: the substitution holds float32's accuracy there."""
    l = strictly_lower(kind, n)
    want = np.linalg.inv(np.eye(n) + l)
    got = jax.jit(unit_lower_inverse)(jnp.asarray(l, jnp.float32))
    assert got.dtype == jnp.float32 and got.shape == l.shape
    # the triangle above the diagonal is exact, the diagonal is 1
    np.testing.assert_array_equal(np.triu(got), np.broadcast_to(
        np.eye(n, dtype=np.float32), got.shape))
    # float32 against float64: relative to the inverse's largest entry
    # (a random l of 64 rows has one of 1e10)
    np.testing.assert_allclose(got, want, rtol=0,
                               atol=1e-5 * np.max(np.abs(want)))


def test_unit_lower_inverse_reads_the_strict_lower_part_alone():
    l = np.random.RandomState(0).randn(2, 24, 24)
    full = unit_lower_inverse(jnp.asarray(l, jnp.float32))
    lower = unit_lower_inverse(jnp.asarray(np.tril(l, -1), jnp.float32))
    np.testing.assert_array_equal(full, lower)


@pytest.mark.parametrize("n", [8, 16, 24, 64])
@pytest.mark.parametrize("kind", ["random", "all_1.99"])
def test_unit_lower_inverse_backward_rule(kind, n):
    """The rule written out (two products) against JAX's derivative of
    ``jnp.linalg.inv`` through the same mask, under one cotangent."""
    scale = 1.0 if kind == "all_1.99" else 0.2   # keeps inv(I + l) O(1)
    l = jnp.asarray(scale * strictly_lower(kind, n), jnp.float32)
    w = jnp.asarray(np.random.RandomState(1).randn(*l.shape), jnp.float32)
    eye = jnp.eye(n, dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ours = jax.jit(jax.grad(
            lambda a: jnp.sum(w * unit_lower_inverse(a))))(l)
        ref = jax.jit(jax.grad(lambda a: jnp.sum(
            w * jnp.linalg.inv(eye + jnp.tril(a, -1)))))(l)
    np.testing.assert_array_equal(np.triu(ours), 0.0)
    np.testing.assert_allclose(
        ours, ref, rtol=0, atol=2e-5 * float(jnp.max(jnp.abs(ref))))


def test_inverse_carries_its_own_name_inside_the_models_scope():
    """Forward, recomputed and backward operations of the inverse hold
    ``delta.inverse`` in their names (what the benchmark's
    ``round_delta_rule_inverse_device_s`` reads), and the innermost
    ``lm.*`` scope of each is still the caller's, so the scope's own
    seconds keep counting them."""
    from benchmark.harness import scope_reduce

    def loss(*a):
        with jax.named_scope("lm.delta_rule"):
            return jnp.sum(chunk_gated_delta_rule(*a, chunk=CHUNK))

    step = jax.grad(jax.checkpoint(loss), argnums=range(5))
    text = jax.jit(step).lower(*inputs("mixed", 32)).as_text(
        debug_info=True)
    names = set(re.findall(r'loc\("([^"]*delta\.inverse[^"]*)"', text))
    assert any("rematted_computation" in n for n in names), names
    assert any("transpose" in n for n in names), names
    assert {scope_reduce.scope_of(n) for n in names} == {"lm.delta_rule"}
