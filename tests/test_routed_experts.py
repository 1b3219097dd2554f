"""``ops/routed_experts.py:expert_share`` over row blocks: the work runs
over the blocks of the dispatch buffer that hold pairs routed here (the
first always, the further ones in a loop whose trips follow the count),
and result and every gradient are a per-token dense reference's,
whatever is routed here: nothing, one pair, a block less one, a block,
a block and one, several blocks with an expert's group across a
boundary, every pair."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from fedtorch_tpu.ops import routed_experts

TOKENS, PER_TOKEN, ROUTED, FIRST, HELD, D, F = 24, 2, 8, 2, 3, 16, 12
BLOCK = 8
ROWS = TOKENS * PER_TOKEN
NAMES = ("mlp.gate", "mlp.up", "mlp.down")
# the pairs routed here: 0, 1, B - 1, B, B + 1, 2.5 blocks (groups of
# 7, 7 and 6 rows: the second lies across row 8, the third across 16),
# every pair (6 blocks)
COUNTS = [0, 1, BLOCK - 1, BLOCK, BLOCK + 1, 20, ROWS]


def case(pairs: int, seed: int = 0):
    """Tokens, gates, a choice of experts with exactly ``pairs`` of the
    token-expert pairs at the experts held (2, 3, 4 of 8), spread over
    the tokens by the seed, and the held experts' weights."""
    rng = np.random.RandomState(seed)
    chosen = np.tile(np.asarray([[0, 1]], np.int32), (TOKENS, 1))
    for p in rng.permutation(ROWS)[:pairs]:
        t, j = divmod(int(p), PER_TOKEN)
        chosen[t, j] = FIRST + (t + j) % HELD     # a token's two differ
    keys = jax.random.split(jax.random.key(seed), 5)
    p = {"gate": 0.3 * jax.random.normal(keys[0], (HELD, D, F)),
         "up": 0.3 * jax.random.normal(keys[1], (HELD, D, F)),
         "down": 0.3 * jax.random.normal(keys[2], (HELD, F, D))}
    u = jax.random.normal(keys[3], (TOKENS, D))
    gates = jax.nn.softmax(jax.random.normal(keys[4], (TOKENS, PER_TOKEN)))
    return p, u, gates, jnp.asarray(chosen)


def dense(p, u, gates, chosen, dt=jnp.float32):
    """Every token through every held expert's SwiGLU, a token's result
    the gated sum over the experts it chose: no buffer, no sort."""
    dot = lambda a, w: jnp.dot(a.astype(dt), w.astype(dt),
                               preferred_element_type=jnp.float32)
    out = 0.0
    for e in range(HELD):
        g = jnp.sum(jnp.where(chosen == FIRST + e, gates, 0.0), axis=-1)
        h = jax.nn.silu(dot(u, p["gate"][e])) * dot(u, p["up"][e])
        out = out + g[:, None] * dot(h, p["down"][e])
    return out


def blocked(p, u, gates, chosen, dt=jnp.float32, block=BLOCK):
    return routed_experts.expert_share(p, u, gates, chosen, first=FIRST,
                                       dt=dt, block=block)


def weighed(fn, c):
    """``fn``'s result against fixed weights, as a scalar to
    differentiate; the counters beside it."""
    def loss(p, u, gates, chosen):
        out = fn(p, u, gates, chosen)
        out, counters = out if isinstance(out, tuple) else (out, {})
        return jnp.sum(out * c), (out, counters)
    return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)


def visited(pairs: int, block: int = BLOCK) -> int:
    """The first block always, the further ones while pairs are left."""
    return max(1, -(-pairs // block)) * block


def gap(got, want):
    return max(float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                     - jnp.asarray(b, jnp.float32))))
               for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)))


@pytest.mark.parametrize("dt,atol", [(jnp.float32, 3e-5),
                                     (jnp.bfloat16, 6e-2)])
@pytest.mark.parametrize("pairs", COUNTS)
def test_result_and_gradients_are_the_dense_references(pairs, dt, atol):
    """float32: the reference's numbers. bfloat16 operands: the
    reference with the same operands (float32 accumulation, gates and
    combine in float32 in both), whose cotangents round in another
    order."""
    p, u, gates, chosen = case(pairs)
    c = jax.random.normal(jax.random.key(9), u.shape)
    with jax.default_matmul_precision("highest"):
        (_, (want, _)), want_grads = weighed(
            lambda *a: dense(*a, dt=dt), c)(p, u, gates, chosen)
        (_, (got, counters)), grads = jax.jit(weighed(
            lambda *a: blocked(*a, dt=dt), c))(p, u, gates, chosen)
    assert float(counters["pairs"]) == pairs
    assert float(counters["rows_visited"]) == visited(pairs)
    np.testing.assert_allclose(got, want, atol=atol)
    assert gap(grads, want_grads) < atol
    assert all(bool(jnp.all(jnp.isfinite(g)))
               for g in jax.tree.leaves(grads))
    assert grads[0]["gate"].dtype == p["gate"].dtype
    # a pair routed elsewhere moves nothing here
    away = np.asarray(chosen < FIRST) | np.asarray(chosen >= FIRST + HELD)
    assert float(jnp.max(jnp.abs(jnp.where(away, grads[2], 0.0)))) == 0.0


@pytest.mark.parametrize("kept", [NAMES, NAMES[:2], ()])
@pytest.mark.parametrize("pairs", [0, BLOCK + 1, 20, ROWS])
def test_under_the_layers_checkpoint(pairs, kept):
    """The layer's policy keeps the first block's three products by
    name, two of them, or none: the same numbers."""
    p, u, gates, chosen = case(pairs, seed=1)
    c = jax.random.normal(jax.random.key(8), u.shape)
    policy = jax.checkpoint_policies.save_only_these_names(*kept)
    layer = jax.checkpoint(blocked, policy=policy if kept else None)
    with jax.default_matmul_precision("highest"):
        (_, (want, _)), want_grads = weighed(dense, c)(p, u, gates, chosen)
        run = weighed(layer, c)
        (_, (got, counters)), grads = jax.jit(run)(p, u, gates, chosen)
    assert float(counters["rows_visited"]) == visited(pairs)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert gap(grads, want_grads) < 3e-5


def test_the_kept_residuals_are_a_blocks_rows(capsys):
    """With all three names kept the float32 residuals of the products
    are ``[block, F]`` twice and ``[block, D]`` once: no array of the
    buffer's ``tokens x per_token`` rows is kept."""
    p, u, gates, chosen = case(20)
    policy = jax.checkpoint_policies.save_only_these_names(*NAMES)
    layer = jax.checkpoint(lambda p, u, gates: blocked(
        p, u, gates, chosen)[0], policy=policy)
    jax.ad_checkpoint.print_saved_residuals(layer, p, u, gates)
    kept = [line.split(" ")[0] for line in
            capsys.readouterr().out.splitlines() if "argument" not in line]
    assert kept.count(f"f32[{BLOCK},{F}]") == 2
    assert kept.count(f"f32[{BLOCK},{D}]") == 1
    assert not [shape for shape in kept if shape.startswith(f"f32[{ROWS},")]


def test_under_vmap_each_element_counts_its_own_trips():
    """A batched loop runs to the longest trip count; each element's
    result, gradients and counters are its own."""
    counts = [0, BLOCK + 1, ROWS, 5]
    cases = [case(n, seed=i) for i, n in enumerate(counts)]
    stack = lambda i: jax.tree.map(lambda *x: jnp.stack(x),
                                   *[each[i] for each in cases])
    p, u, gates, chosen = (stack(i) for i in range(4))
    c = jax.random.normal(jax.random.key(7), u.shape[1:])
    with jax.default_matmul_precision("highest"):
        (_, (got, counters)), grads = jax.jit(jax.vmap(weighed(blocked, c)))(
            p, u, gates, chosen)
        for i, each in enumerate(cases):
            (_, (want, _)), want_grads = weighed(dense, c)(*each)
            np.testing.assert_allclose(got[i], want, atol=3e-5)
            assert gap(jax.tree.map(lambda g: g[i], grads),
                       want_grads) < 3e-5
    np.testing.assert_array_equal(counters["pairs"], counts)
    np.testing.assert_array_equal(counters["rows_visited"],
                                  [visited(n) for n in counts])


@pytest.mark.parametrize("block", [1, 5, ROWS, ROWS + 7, None])
def test_any_block_gives_the_same_result(block):
    """A block of one row, one that does not divide the buffer, the
    buffer itself, more than the buffer, and the default (the buffer in
    one block: no loop is built)."""
    p, u, gates, chosen = case(20, seed=2)
    c = jax.random.normal(jax.random.key(6), u.shape)
    with jax.default_matmul_precision("highest"):
        (_, (want, _)), want_grads = weighed(dense, c)(p, u, gates, chosen)
        (_, (got, counters)), grads = jax.jit(weighed(
            lambda *a: blocked(*a, block=block), c))(p, u, gates, chosen)
    rows = min(block or ROWS, ROWS)
    assert float(counters["rows_visited"]) == visited(20, rows)
    np.testing.assert_allclose(got, want, atol=3e-5)
    assert gap(grads, want_grads) < 3e-5


@pytest.mark.parametrize("tokens,per_token,held,routed,want", [
    (4096, 8, 16, 128, 8192),      # the keye cell: 4096 pairs expected
    (4096, 6, 16, 128, 6144),      # the kanana cell: 3072
    (8192, 8, 16, 128, 16384),
    (4096, 8, 128, 128, 32768),    # every expert held: the buffer
    (40, 2, 4, 8, 80),             # under a row tile: the buffer
    (1000, 8, 16, 128, 2048),      # whole row tiles of 512
])
def test_the_block_comes_from_shapes(tokens, per_token, held, routed, want):
    assert routed_experts.block_rows(tokens, per_token, held, routed) == want
    assert want <= tokens * per_token
