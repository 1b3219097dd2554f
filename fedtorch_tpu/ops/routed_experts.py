"""One chip's share of a sparse-expert layer: the router scores every
published expert, a token goes to its ``per_token`` best, and this chip
computes the part of the result that the experts it HOLDS give.

    r = softmax(W_r u)  over all ``routed`` experts
    T = the ``per_token`` largest of r;  g_e = r_e / sum_{e' in T} r_e'
    out(u) = sum over e in T and held of g_e W_d^e (silu(W_g^e u) * W_u^e u)

``g`` is normalised over all of ``T`` as published, so the shares of
all chips add up to the whole layer; what absent experts would add is
left out, and nothing stands in for the other chips or their exchange.

:func:`route` also writes DeepSeek-V3's router (``scoring='sigmoid'``):
``s = sigmoid(W_r u)``, ``T`` the ``per_token`` largest of ``s + b``
with ``b`` a per-expert bias that no gradient reaches, ``g_e = scale x
s_e / (sum_{e' in T} s_e' + 1e-20)`` from the unbiased scores.
:func:`load_over_all` counts a step's pairs over ALL routed experts and
:func:`balance_step` is the loss part whose gradient is the published
auxiliary-loss-free update of ``b`` (``topk_method`` ``noaux_tc``).
:func:`plan` and :func:`expert_share` serve both routers as they are;
a shared expert beside the routed ones is the model's
(``models/hybrid_lm.py``), not this file's: every chip computes it
alike.

Dropless under any imbalance: the token-expert pairs routed here are
sorted by expert to the front of ONE buffer of ``tokens x per_token``
rows, the worst case (every pair held here). No array of that many rows
is filled, masked, activated or multiplied: the work runs over ROW
BLOCKS of a static size (:func:`block_rows`: ``BLOCK_MULTIPLE`` times
the pairs expected here, from shapes), the first always and each
further one only while pairs are left, a ``lax.while_loop`` whose trips
follow ``sum(sizes)``. A block's trip is the whole layer for its rows:
the rows' tokens gathered, the three grouped products
(``jax.lax.ragged_dot``: on the TPU a grouped-matmul kernel that visits
the row tiles in use) with the groups' sizes clipped to the block's
window (a group across a boundary is two groups of the same expert),
the activation, and the rows summed back to their tokens with their
gates: a gather of at most ``per_token`` rows a token inside its sum,
no scatter. With every pair held here all ``tokens x per_token`` rows
are computed, block by block. Rows of a block past the pairs hold
whatever the kernel left: each consumer selects them away, and what the
kernels read of them is zeros or real tokens' rows.

One backward rule for the whole share (:func:`_blocked_share`): the
first block's three product results carry the names a rematerialized
layer's policy keeps (``mlp.gate`` / ``mlp.up`` / ``mlp.down``: a
block's rows, not the buffer's), the further blocks keep nothing and
compute their forward again inside the backward loop; a weight's
gradient is summed over the blocks in float32 and handed back in the
weight's own type.

Read on the chip (v5e; one layer call forward, recomputation under the
layer's checkpoint with gate and up kept, and backward, 4096 tokens of
2048, 16 experts of 768 held of 128, 8 a token, the router's product
and top-8 included; PERF.md section 6, PR 43): the whole buffer as it
was 18.05 ms with the expected 3 962 pairs here; in blocks of 4096 /
6144 / 8192 rows 7.47 / 8.02 / 8.13 ms, of 32 768 (one block) 13.94.
A further trip costs 5-7 ms (with 11 585 pairs: 19.0 / 15.6 / 15.5 ms
in blocks of 4096 / 6144 / 8192, 20.6 as it was; with all 32 768:
42.7 ms in six trips of 6144, 32.5 in four of 8192, 27.5 as it was:
the worst case is dearer than one straight line, the price of
following the count), a wider block next to nothing (0.24 ms a
thousand rows), and the pairs a LAYER holds spread far more than a
round's mean over its layers (blocks of 1.5 times the expectation took
a second trip in two of five layer calls of the benchmark's cell):
hence ``BLOCK_MULTIPLE`` 2. Three forms of the sum back to the tokens
were timed alone on a block of 6144 float32 rows: the gather inside the
sum 0.90 ms (XLA:TPU writes the gathered ``[per_token, tokens, D]``
array and reads it back: it does not fuse a gather into its consumer),
a scatter-add of the rows 0.63 alone but 0.26 ms slower in the layer
(its select and its gates are passes of their own), rows brought into
token order and neighbours added by shifted selects 1.20: the gather
stayed. The first block stands outside the loop because a loop that
holds it starts the three float32 weight gradients at zero and adds
into them (1.4 GiB of traffic a layer call) and keeps residuals of the
buffer's size that a zero-fill writes whole.

The pairs routed here follow the data and the router (3 800 to 4 700 a
row-step, mean over the layers, within one run of the benchmark's cell,
whose expectation is 4 096), and a round's seconds follow them in steps
of a block: the round's row carries the count and the rows worked over
(``lm_moe_pairs_local``, ``lm_moe_rows_visited``).

Types: operands of the router's and the experts' products in the
caller's compute type, accumulated in float32; router probabilities,
gates and the combine in float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


class Plan(NamedTuple):
    """Where each token-expert pair sits in the buffer."""
    order: jax.Array       # [N * k] slot -> pair (token * k + choice)
    slot: jax.Array        # [N * k] pair -> slot
    sizes: jax.Array       # [held] rows of each held expert's group
    live: jax.Array        # [N * k] slot holds a pair routed here
    here: jax.Array        # [N, k] pair is routed to an expert held


def route(logits, per_token: int, normalise: bool, *,
          scoring: str = "softmax", bias=None, scale: float = 1.0):
    """Router ``logits`` [N, routed] float32 -> (gates [N, k] float32,
    experts [N, k] int32): the ``k`` best experts a token and their
    gates. ``scoring`` 'softmax' (the default: the scores are the
    probabilities over all experts) or 'sigmoid' (each expert's own,
    DeepSeek-V3's). The choice is by score plus ``bias`` [routed] where
    one is given (no gradient reaches it: the choice has none), the
    gates are the chosen experts' UNBIASED scores, which ``normalise``
    makes sum to 1 (over ``sum + 1e-20`` where the scores are sigmoids,
    as published) and ``scale`` (``routed_scaling_factor``) multiplies.
    With the defaults the program is the softmax router's as it was."""
    logits = logits.astype(jnp.float32)
    # lint: disable=FTL005 — a string of the model's file
    if scoring == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"scoring must be 'softmax' or 'sigmoid', got "
                         f"{scoring!r}")
    # lint: disable=FTL005 — a bias leaf or none, by the model's file
    if bias is None:
        gates, experts = jax.lax.top_k(scores, per_token)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)),
            per_token)
        gates = jnp.take_along_axis(scores, experts, axis=-1)
    # lint: disable=FTL005 — a flag of the model's file
    if normalise:
        total = jnp.sum(gates, axis=-1, keepdims=True)
        # lint: disable=FTL005 — a string of the model's file
        gates = gates / (total + 1e-20 if scoring == "sigmoid" else total)
    # lint: disable=FTL005 — a host float of the model's file
    if scale != 1.0:
        gates = gates * scale
    return gates, experts


def load_over_all(experts, routed: int):
    """``c`` [routed] float32: the pairs of ``experts`` [N, k] that
    chose each of the ``routed`` experts, held here or not: this chip's
    tokens over the whole router (in a deployment the group's chips
    would sum their counts)."""
    hot = experts.reshape(-1)[:, None] == jnp.arange(routed)[None, :]
    return jnp.sum(hot.astype(jnp.float32), axis=0)


def balance_step(bias, load):
    """DeepSeek-V3's auxiliary-loss-free balance as a loss part: ``-sum_e
    b_e sign(mean(c) - c_e)`` with the sign under ``stop_gradient``, so
    that its gradient with respect to ``bias`` [routed] is minus the
    published step's direction (a plain SGD step of size ``lr x u``
    then moves ``b_e`` by ``gamma sign(mean(c) - c_e)``, ``gamma = lr x
    u``) and no other leaf receives anything. The caller adds it as
    ``u (L - stop_gradient(L))``: value zero."""
    direction = jax.lax.stop_gradient(jnp.sign(jnp.mean(load) - load))
    return -jnp.sum(bias.astype(jnp.float32) * direction)


def plan(experts, first: int, held: int) -> Plan:
    """Sort the pairs whose expert is one of ``first .. first + held -
    1`` by expert (stable: by token within an expert) to the front of
    the buffer; every other pair goes behind them. A counting sort:
    a pair's slot is its expert's first row plus the pairs of that
    expert before it (a running count down the pairs), no comparison
    sort."""
    local = experts - first
    here = (local >= 0) & (local < held)
    key = jnp.where(here, local, held).reshape(-1)
    hot = (key[:, None] == jnp.arange(held + 1)[None, :]).astype(jnp.int32)
    counts = jnp.sum(hot, axis=0)
    before = jnp.cumsum(hot, axis=0) - hot
    slot = jnp.sum((before + (jnp.cumsum(counts) - counts)[None, :]) * hot,
                   axis=1)
    pairs = jnp.arange(key.shape[0], dtype=jnp.int32)
    order = jnp.zeros_like(pairs).at[slot].set(pairs, unique_indices=True)
    sizes = counts[:held]
    return Plan(order, slot, sizes, pairs < jnp.sum(sizes), here)


_RAGGED_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def _one_by_one(product):
    """``product(a, b, sizes)`` with ``vmap`` written out as a loop
    over the batch: ``ragged_dot``'s own batching rule takes operands
    that all carry their batch in front and refuses the rest (a buffer
    that a batched gather filled does not)."""
    product = jax.custom_batching.custom_vmap(product)

    @product.def_vmap
    def rule(axis_size, in_batched, *args):
        args = [a if batched else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, batched in zip(args, in_batched)]
        return jax.lax.map(lambda each: product(*each), tuple(args)), True

    return product


@_one_by_one
def _rows_by_group(x, w, sizes):
    """[M, K] x [G, K, N] -> float32 [M, N], group ``e``'s rows
    against ``w[e]``."""
    return jax.lax.ragged_dot(x, w, sizes,
                              preferred_element_type=jnp.float32)


@_one_by_one
def _groups_of_rows(x, g, sizes):
    """[M, K] x [M, N] -> float32 [G, K, N], each group's rows
    contracted."""
    return jax.lax.ragged_dot_general(
        x, g, sizes, _RAGGED_ROWS, preferred_element_type=jnp.float32)


@jax.custom_vjp
def grouped_dot(x, w, sizes):
    """``x`` [M, K] against each group's own ``w[e]`` [K, N], rows
    ``sum(sizes[:e]) .. sum(sizes[:e + 1]) - 1`` being group ``e``'s:
    float32 [M, N]. The backward rule is the two grouped products
    written out, cotangents rounded to the operands' type as a plain
    product's are."""
    return _rows_by_group(x, w, sizes)


def _grouped_fwd(x, w, sizes):
    return _rows_by_group(x, w, sizes), (x, w, sizes)


def _grouped_bwd(res, g):
    x, w, sizes = res
    g = g.astype(x.dtype)
    dx = _rows_by_group(g, jnp.swapaxes(w, 1, 2), sizes)
    dw = _groups_of_rows(x, g, sizes)
    return dx.astype(x.dtype), dw.astype(w.dtype), None


grouped_dot.defvjp(_grouped_fwd, _grouped_bwd)


# One trip's rows of the buffer, as a multiple of the pairs expected
# here (``tokens x per_token x held / routed``), rounded up to the
# grouped kernel's row tile.
BLOCK_MULTIPLE = 2.0
ROW_TILE = 512


def block_rows(tokens: int, per_token: int, held: int, routed: int) -> int:
    """The row block of :func:`expert_share` for a layer of these
    shapes: ``BLOCK_MULTIPLE`` times the pairs expected at the experts
    held, in whole row tiles, at most the buffer."""
    rows = tokens * per_token
    tiles = -(-int(BLOCK_MULTIPLE * rows * held) // (routed * ROW_TILE))
    return max(1, min(tiles * ROW_TILE, rows))


class _Block(NamedTuple):
    """Rows ``[c x block, (c + 1) x block)`` of the buffer."""
    token: jax.Array       # [block] the row's token
    gate: jax.Array        # [block] float32, the row's gate; 0 past the pairs
    live: jax.Array        # [block, 1] the row holds a pair routed here
    sizes: jax.Array       # [held] each group's rows inside the block
    index: jax.Array       # [k, N] the pair's row of the block (clipped)
    valid: jax.Array       # [k, N] the pair sits in this block


def _block(c, gates, order, slot, sizes, here, block: int) -> _Block:
    """The plan as block ``c`` sees it: a group that straddles two
    blocks is two groups of the same expert."""
    k = here.shape[1]
    lo = c * block
    pair = jax.lax.dynamic_slice_in_dim(order, lo, block)
    live = lo + jnp.arange(block, dtype=jnp.int32) < jnp.sum(sizes)
    ends = jnp.cumsum(sizes)
    # choice-major: a token's rows are summed over the leading axis,
    # whole [N, D] slabs whatever ``k`` is
    index = slot.reshape(here.shape).T - lo
    return _Block(
        pair // k, jnp.where(live, gates.reshape(-1)[pair], 0.0),
        live[:, None],
        jnp.clip(ends, lo, lo + block) - jnp.clip(ends - sizes, lo,
                                                  lo + block),
        jnp.clip(index, 0, block - 1),
        here.T & (index >= 0) & (index < block))


def _activation(a, b):
    return jax.nn.silu(a) * b


def _block_forward(bl: _Block, u, w, dt, fill: str, names=False):
    """The block's rows through the three grouped products: (x, gate's,
    up's and down's results); the rows' fill from the tokens under the
    scope ``fill``. The products' rows past the pairs are whatever the
    kernel left: every consumer selects them away."""
    # the first block's results carry the policy's names
    # lint: disable=FTL005 — a static flag of the caller
    name = checkpoint_name if names else (lambda x, _: x)
    with jax.named_scope(fill):
        x = u[bl.token]
    a = name(_rows_by_group(x, w[0], bl.sizes), "mlp.gate")
    b = name(_rows_by_group(x, w[1], bl.sizes), "mlp.up")
    h = jnp.where(bl.live, _activation(a, b), 0.0).astype(dt)
    y = name(_rows_by_group(h, w[2], bl.sizes), "mlp.down")
    return x, a, b, y


def _to_tokens(rows, bl: _Block, weights=None):
    """Block rows [block, D] -> float32 [N, D]: a token's rows of the
    block summed (times ``weights`` [N, k]). A gather of at most
    ``per_token`` rows a token inside its sum; no scatter."""
    picked = jnp.where(bl.valid[..., None], rows[bl.index], 0.0)
    picked = picked.astype(jnp.float32)
    # lint: disable=FTL005 — weights or none, by the caller
    if weights is not None:
        picked = picked * weights.T[..., None]
    return jnp.sum(picked, axis=0)


def _block_backward(bl: _Block, u, w, g, dt, fill: str, kept=None):
    """Block ``c``'s part of the cotangents of (u [N, D] float32,
    gates [N, k], the three weights float32) from the result's ``g``
    [N, D] float32; ``kept``: the forward products' (gate, up, down)
    results where they were kept, else they are computed again."""
    # lint: disable=FTL005 — residuals or none, by the caller
    if kept is None:
        x, *kept = _block_forward(bl, u, w, dt, fill)
    else:
        with jax.named_scope(fill):
            x = u[bl.token]
    a, b, y = kept
    with jax.named_scope(fill):
        g_rows = g[bl.token]
    d_gate_rows = jnp.sum(y * g_rows, axis=-1)
    d_gates = jnp.where(bl.valid, d_gate_rows[bl.index], 0.0).T
    gy = (bl.gate[:, None] * g_rows).astype(dt)
    h, pull = jax.vjp(_activation, a, b)
    h = jnp.where(bl.live, h, 0.0).astype(dt)
    dh = _rows_by_group(gy, jnp.swapaxes(w[2], 1, 2), bl.sizes)
    da, db = (jnp.where(bl.live, d, 0.0).astype(dt) for d in pull(dh))
    dx = _rows_by_group(da, jnp.swapaxes(w[0], 1, 2), bl.sizes) \
        + _rows_by_group(db, jnp.swapaxes(w[1], 1, 2), bl.sizes)
    dw = (_groups_of_rows(x, da, bl.sizes),
          _groups_of_rows(x, db, bl.sizes),
          _groups_of_rows(h, gy, bl.sizes))
    return _to_tokens(dx.astype(dt), bl), d_gates, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9))
def _blocked_share(u, gates, w, order, slot, sizes, here, block: int, dt,
                   scopes):
    """The share of tokens ``u`` [N, D] (compute type) with ``gates``
    [N, k] (0 where the pair is not routed here) through the held
    experts' weights ``w`` (gate, up, down), over the plan's row blocks:
    float32 [N, D]. ``order`` is padded to whole blocks."""
    return _blocked_fwd(u, gates, w, order, slot, sizes, here, block, dt,
                        scopes)[0]


def _rest(sizes, block: int, rows: int, trip, first):
    """``first`` (block 0's result) and the further blocks' added to
    it: ``trip(c)`` for every ``c >= 1`` whose rows begin before the
    last pair routed here. No loop is built where one block is the
    buffer."""
    # lint: disable=FTL005 — host integers from shapes
    if block >= rows:
        return first
    pairs = jnp.sum(sizes)
    return jax.lax.while_loop(
        lambda carry: carry[0] * block < pairs,
        lambda carry: (carry[0] + 1,
                       jax.tree.map(jnp.add, carry[1], trip(carry[0]))),
        (jnp.ones((), jnp.int32), first))[1]


def _blocked_fwd(u, gates, w, order, slot, sizes, here, block: int, dt,
                 scopes):
    fill, work = scopes
    plan_ = (gates, order, slot, sizes, here)
    with jax.named_scope(work):
        cast = tuple(each.astype(dt) for each in w)

        def trip(c, names=False):
            with jax.named_scope(fill):
                bl = _block(c, *plan_, block)
            _, *kept = _block_forward(bl, u, cast, dt, fill, names)
            return _to_tokens(kept[2], bl, gates), kept

        first, kept = trip(0, names=True)
        out = _rest(sizes, block, order.shape[0], lambda c: trip(c)[0],
                    first)
    return out, (u, w, cast, plan_, kept)


def _blocked_bwd(block: int, dt, scopes, res, g):
    fill, work = scopes
    u, w, cast, plan_, kept = res
    _, order, _, sizes, _ = plan_
    with jax.named_scope(work):
        g = g.astype(jnp.float32)

        def trip(c, kept=None):
            with jax.named_scope(fill):
                bl = _block(c, *plan_, block)
            return _block_backward(bl, u, cast, g, dt, fill, kept)

        du, d_gates, dw = _rest(sizes, block, order.shape[0], trip,
                                trip(0, kept))
    return (du.astype(u.dtype), d_gates,
            tuple(d.astype(each.dtype) for d, each in zip(dw, w)),
            None, None, None, None)


_blocked_share.defvjp(_blocked_fwd, _blocked_bwd)


def expert_share(p, u, gates, experts, *, first: int, dt,
                 block: Optional[int] = None,
                 scopes=("router", "experts")):
    """The held experts' part of the layer's result for tokens ``u``
    [N, D]: ``p`` holds ``gate`` / ``up`` [held, D, F] and ``down``
    [held, F, D]; ``gates`` / ``experts`` [N, k] are :func:`route`'s.
    ``block``: the rows of the buffer a trip runs over
    (:func:`block_rows`; default: the whole buffer in one). Returns
    (out [N, D] float32, {"pairs": pairs computed here, "rows_visited":
    the buffer rows the work ran over, ``block`` times the trips,
    "load_max_over_mean": the fullest held expert's rows over the mean,
    0 where none is routed here}). ``scopes``: the names the device
    trace gives the sort and the blocks' fill, and the grouped products
    and the combine."""
    n, k = experts.shape
    held = p["gate"].shape[0]
    block = n * k if block is None else min(block, n * k)
    with jax.named_scope(scopes[0]):
        pl = plan(experts, first, held)
        pairs = jnp.sum(pl.sizes)
        # whole blocks: the last one's window stays inside the array
        order = jnp.pad(pl.order, (0, -(n * k) % block))
        u, gates = u.astype(dt), jnp.where(pl.here, gates, 0.0)
    out = _blocked_share(u, gates, (p["gate"], p["up"], p["down"]), order,
                         pl.slot, pl.sizes, pl.here, block, dt,
                         tuple(scopes))
    trips = jnp.maximum(-(-pairs // block), 1)
    pairs = pairs.astype(jnp.float32)
    load = jnp.max(pl.sizes).astype(jnp.float32) * held \
        / jnp.maximum(pairs, 1.0)
    return out, {"pairs": pairs,
                 "rows_visited": (trips * block).astype(jnp.float32),
                 "load_max_over_mean": load}
