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
:func:`plan`, :func:`dispatch`, the grouped products and
:func:`combine` serve both routers as they are; a shared expert beside
the routed ones is the model's (``models/hybrid_lm.py``), not this
file's: every chip computes it alike.

Dropless under any imbalance: the token-expert pairs routed here are
sorted by expert into ONE buffer of ``tokens x per_token`` rows, the
worst case (every pair held here), and the three grouped products
(``jax.lax.ragged_dot``: on the TPU a grouped-matmul kernel that visits
the row tiles in use) run over the groups' rows; rows past the last
group are never read as results (they are selected away, whatever the
kernel left there). Dispatch and combine are gathers in both
directions: a permutation's transpose is its inverse, so no scatter
runs forward or backward.

The products' seconds follow the rows they visit (v5e, 4096 tokens, 16
experts of 2048 x 768 held, forward and backward: 13.9 ms at 4096 rows,
16.7 at 16 384, 21.0 at the buffer's 32 768), and the pairs routed here
follow the data and the router (3 800 to 4 700 a row-step and layer
within one run of the benchmark's cell, whose expectation is 4 096;
1 800 to 5 600 while the seeded embedding was small beside the first
attention's output and a row's tokens all went the same way:
``models/hybrid_lm.py``, ``embedding_init_std``), so a round's seconds
follow the routing: the round's row carries the count
(``lm_moe_pairs_local``).

Types: operands of the router's and the experts' products in the
caller's compute type, accumulated in float32; router probabilities,
gates and the combine in float32.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

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


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch(u, order, slot, k: int):
    """Tokens ``u`` [N, D] -> the buffer [N * k, D]: slot ``i`` holds
    the token of pair ``order[i]``."""
    return u[order // k]


def _dispatch_fwd(u, order, slot, k):
    return u[order // k], (slot, u.shape[0])


def _dispatch_bwd(k, res, g):
    slot, n = res
    # a token's cotangent: its k slots' rows, summed in float32
    rows = g[slot].reshape(n, k, g.shape[-1]).astype(jnp.float32)
    return jnp.sum(rows, axis=1).astype(g.dtype), None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(y, order, slot):
    """The buffer ``y`` [N * k, D] back in the pairs' own order."""
    return y[slot]


def _combine_fwd(y, order, slot):
    return y[slot], order


def _combine_bwd(order, g):
    return g[order], None, None


combine.defvjp(_combine_fwd, _combine_bwd)


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


def _grouped(x, w, sizes, live, dt, name: str):
    """:func:`grouped_dot` in the compute type, rows of no group zero
    (whatever the kernel left there), the result named for a
    rematerialized layer's policy."""
    out = grouped_dot(x.astype(dt), w.astype(dt), sizes)
    return checkpoint_name(jnp.where(live[:, None], out, 0.0), name)


def expert_share(p, u, gates, experts, *, first: int, dt,
                 scopes=("router", "experts")):
    """The held experts' part of the layer's result for tokens ``u``
    [N, D]: ``p`` holds ``gate`` / ``up`` [held, D, F] and ``down``
    [held, F, D]; ``gates`` / ``experts`` [N, k] are :func:`route`'s.
    Returns (out [N, D] float32, {"pairs": pairs computed here,
    "load_max_over_mean": the fullest held expert's rows over the
    mean, 0 where none is routed here}). ``scopes``: the names the
    device trace gives the sort and the buffer's fill, and the grouped
    products and the combine."""
    n, k = experts.shape
    held = p["gate"].shape[0]
    with jax.named_scope(scopes[0]):
        pl = plan(experts, first, held)
        x = dispatch(u.astype(dt), pl.order, pl.slot, k)
        x = jnp.where(pl.live[:, None], x, jnp.zeros((), dt))
    with jax.named_scope(scopes[1]):
        grouped = functools.partial(_grouped, sizes=pl.sizes,
                                    live=pl.live, dt=dt)
        h = jax.nn.silu(grouped(x, p["gate"], name="mlp.gate")) \
            * grouped(x, p["up"], name="mlp.up")
        y = combine(grouped(h, p["down"], name="mlp.down"), pl.order,
                    pl.slot)
        out = jnp.sum(y.reshape(n, k, -1)
                      * jnp.where(pl.here, gates, 0.0)[..., None], axis=1)
    pairs = jnp.sum(pl.sizes).astype(jnp.float32)
    load = jnp.max(pl.sizes).astype(jnp.float32) * held \
        / jnp.maximum(pairs, 1.0)
    return out, {"pairs": pairs, "load_max_over_mean": load}
