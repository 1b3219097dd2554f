"""The chunked gated delta rule (``ops/delta_rule.py``) against the
recurrence written out token by token
(``benchmark/reference/olmo_hybrid.py:recurrent_delta_rule``): values
and gradients, with the decay near 0 and near 1, ``beta`` near 2, and
lengths that are and are not a multiple of the chunk."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference.olmo_hybrid import recurrent_delta_rule
from fedtorch_tpu.ops.delta_rule import chunk_gated_delta_rule

B, H, DK, DV, CHUNK = 2, 3, 8, 12, 16

REGIMES = {
    # log decay, beta
    "mixed": lambda r, s: (-0.5 * r.rand(*s), 2.0 * r.rand(*s)),
    "decay_near_0": lambda r, s: (-6.0 - r.rand(*s), 1.0 + r.rand(*s)),
    "decay_near_1_beta_near_2": lambda r, s: (-1e-4 * r.rand(*s),
                                              np.full(s, 1.99)),
}


def inputs(regime, T, seed=0):
    r = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(r.randn(B, T, H, DK)) / np.sqrt(DK)
    k = unit(r.randn(B, T, H, DK))
    v = r.randn(B, T, H, DV)
    g, beta = REGIMES[regime](r, (B, T, H))
    return tuple(jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta))


@pytest.mark.parametrize("T", [64, 100, 13])
@pytest.mark.parametrize("regime", list(REGIMES))
def test_chunked_equals_recurrent_values_and_gradients(regime, T):
    args = inputs(regime, T)
    chunked = lambda *a: chunk_gated_delta_rule(*a, chunk=CHUNK)
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
