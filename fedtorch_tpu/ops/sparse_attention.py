"""Attention over a learned selection of keys (DeepSeek-V3.2-Exp's
sparse attention: a lightning indexer scores every earlier position
for every query, and the query attends to the ``topk`` best).

The indexer has ``J`` query heads and one key head of ``di``:

    I[t, s] = sum_j w[t, j] relu(qI[t, j] . kI[s])          s <= t
    S_t     = the ``topk`` positions s <= t of largest I[t, s]
              (every s <= t while t < topk)
    o[t]    = softmax over s in S_t of (q[t] . k[s] / sqrt(hd)) v[s]

and it is trained by one term alone, which reaches nothing else:

    L_I = mean_t KL(p_t || softmax over S_t of I[t, .])

with ``p_t`` the main attention's probabilities over ``S_t`` summed
over the query heads and normalised to sum 1, under ``stop_gradient``.
The selection passes no gradient (it is a mask).

Written over chunks of query rows so that no ``[heads, T, T]`` array
lives: a chunk's scores are ``[heads, chunk, keys]``. The chunks run in
a few *bands* (``KEY_BANDS``): a band's chunks see the keys up to the
band's last query and no later one, which a causal mask would remove
anyway, so the dense products do 5/8 of the full square's work at four
bands. Each chunk's body is under ``jax.checkpoint``: of a chunk only
its inputs and each query's threshold (``KEPT[0]``) outlive it, and the
backward pass runs the rest again, not the threshold's search. The
output carries a name (``KEPT[1]``) for a rematerialized layer's
policy: a layer that keeps it runs the chunks' attention twice (forward;
again inside the backward pass) and not three times; of the indexer it
still runs scores and search again, because **a threshold kept by the
LAYER's policy gave wrong gradients in jax 0.9.0, silently** (whether
named inside the chunks' checkpoint or in a pass of its own before
them: 29 % off on a leaf, caught by the comparison with the reference
under ``remat``), so the layer keeps the output alone. This is
the masked dense form: unselected pairs are computed and masked. A
kernel that skips unselected key blocks is not written yet.

Types: operands of the four products (indexer scores, ``q k^T``,
``p v``) in the caller's compute type, accumulated in float32; the
weighted sum over the indexer's heads, the threshold, the softmax and
the KL term in float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

# bands of query chunks that share a key length (module docstring)
KEY_BANDS = 4
# the names of what need not be computed again: each query's threshold
# (a float; kept by a chunk's own checkpoint) and the attention's output
# (for a rematerialized layer's policy)
KEPT = ("selection.kth", "selection.out")


def kth_largest(x, k: int):
    """The ``k``-th largest of each row of ``x`` [..., n] float32,
    exactly (``-inf`` where a row holds fewer than ``k`` values above
    ``-inf``; NaN-free input). A bisection over the 32 bits of the
    order-preserving integer image of a float: 32 counting passes over
    ``x``, no sort."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    # negative floats order backwards as integers: flip their low bits
    key = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)

    def step(i, lo):
        # ``lo``: the largest value found so far with at least k keys
        # at or above it, built from the top bit down as an unsigned
        # offset from the smallest int32
        trial = lo + jax.lax.shift_left(
            jnp.uint32(1), (31 - i).astype(jnp.uint32))
        at_least = jnp.sum(_unsigned(key) >= trial[..., None], axis=-1)
        return jnp.where(at_least >= k, trial, lo)

    lo = jax.lax.fori_loop(0, 32, step, jnp.zeros(x.shape[:-1], jnp.uint32))
    out = _signed(lo)
    out = jnp.where(out < 0, out ^ jnp.int32(0x7FFFFFFF), out)
    return jax.lax.bitcast_convert_type(out, jnp.float32)


def _unsigned(key):
    """int32 order -> uint32 order (the smallest int32 becomes 0)."""
    return jax.lax.bitcast_convert_type(key, jnp.uint32) ^ jnp.uint32(
        0x80000000)


def _signed(u):
    return jax.lax.bitcast_convert_type(u ^ jnp.uint32(0x80000000),
                                        jnp.int32)


def index_scores(qi, ki, wi):
    """``I`` of a chunk: ``qi`` [B, C, J, di], ``ki`` [B, S, di] (the
    compute type), ``wi`` [B, C, J] float32 -> [B, C, S] float32."""
    dots = jnp.einsum("bcjd,bsd->bcjs", qi, ki,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * wi[..., None], axis=2)


def select(scores, rows, topk: int):
    """The selection's mask [B, C, S] of a chunk from its indexer
    ``scores`` [B, C, S]: the causal positions (key ``s`` <= the
    query's position, ``rows`` [C]) whose score is among the row's
    ``topk`` largest; all of them where the keys in sight are no more
    than ``topk``. Scores that tie with the ``topk``-th are all taken."""
    S = scores.shape[-1]
    causal = jnp.arange(S)[None, :] <= rows[:, None]
    # lint: disable=FTL005 — static lengths
    if S <= topk:
        return jnp.broadcast_to(causal[None], scores.shape)
    masked = jnp.where(causal[None], scores, -jnp.inf)
    kth = checkpoint_name(
        kth_largest(jax.lax.stop_gradient(masked), topk), KEPT[0])
    return causal[None] & (masked >= kth[..., None])


def _chunk(q, k, v, qi, ki, wi, rows, topk: int, dt, scopes):
    """One chunk of query rows: ``q`` [B, C, H, hd], ``k`` / ``v``
    [B, S, KV, hd], ``qi`` [B, C, J, di], ``ki`` [B, S, di], ``wi``
    [B, C, J], ``rows`` [C] the queries' positions -> (``o``
    [B, C, H, hd] float32, the KL term of each query [B, C])."""
    B, C, H, hd = q.shape
    KV = k.shape[2]
    with jax.named_scope(scopes[0]):
        scores = index_scores(qi.astype(dt), ki.astype(dt), wi)
        sel = select(scores, rows, topk)
    with jax.named_scope(scopes[1]):
        s = jnp.einsum("bckgd,bskd->bkgcs",
                       q.astype(dt).reshape(B, C, KV, H // KV, hd),
                       k.astype(dt), preferred_element_type=jnp.float32) \
            / math.sqrt(hd)
        probs = jax.nn.softmax(jnp.where(sel[:, None, None], s, -jnp.inf),
                               axis=-1)
        o = jnp.einsum("bkgcs,bskd->bckgd", probs.astype(dt), v.astype(dt),
                       preferred_element_type=jnp.float32)
        # the indexer's target: the heads' probabilities summed, of mass 1
        target = jax.lax.stop_gradient(jnp.sum(probs, axis=(1, 2)) / H)
    with jax.named_scope(scopes[0]):
        log_q = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf),
                                   axis=-1)
        # 0 log 0 = 0, and outside the selection log_q is -inf beside a
        # target of 0: both left out by the two guards
        log_p = jnp.log(jnp.where(target > 0, target, 1.0))
        kl = jnp.sum(jnp.where(sel, target * (log_p - jnp.where(
            sel, log_q, 0.0)), 0.0), axis=-1)
    return o.reshape(B, C, H, hd), kl


def chunk_of(T: int, chunk: int) -> int:
    """The query chunk of a ``T``-token row: ``chunk``, or the row
    where it is shorter. Rows longer than a chunk are whole chunks."""
    if T <= chunk:
        return T
    if T % chunk:
        raise ValueError(
            f"selected attention runs over query chunks of {chunk}: a row "
            f"of {T} tokens is no whole number of them")
    return chunk


def selected_attention(q, k, v, qi, ki, wi, *, topk: int, chunk: int, dt,
                       scopes=("indexer", "attention")):
    """``q`` [B, T, H, hd], ``k`` / ``v`` [B, T, KV, hd] (``H`` a
    multiple of ``KV``: query head ``h`` reads key head ``h // (H /
    KV)``), the indexer's ``qi`` [B, T, J, di], ``ki`` [B, T, di] and
    ``wi`` [B, T, J], all float32 (a chunk casts its products'
    operands to ``dt``, so that the chunks' cotangents of ``k``, ``v``
    and ``ki`` add up in float32) -> (``o`` [B, T, H, hd] float32,
    ``L_I`` a scalar: the KL term's mean over the B x T queries).
    ``scopes``: the names the device trace gives the indexer's and the
    attention's operations."""
    B, T = q.shape[:2]
    C = chunk_of(T, chunk)
    n = T // C
    bands = math.gcd(n, KEY_BANDS)
    per = n // bands
    chunked = lambda t, lo: jnp.moveaxis(
        t[:, lo * C:(lo + per) * C].reshape((B, per, C) + t.shape[2:]),
        1, 0)
    joined = lambda parts: jnp.concatenate(
        [jnp.moveaxis(t, 0, 1).reshape((B, per * C) + t.shape[3:])
         for t in parts], axis=1)
    spans = [(band * per, (band + 1) * per * C,
              (band * per * C + jnp.arange(per * C)).reshape(per, C))
             for band in range(bands)]

    body = jax.checkpoint(
        lambda k_, v_, ki_, xs: _chunk(xs[0], k_, v_, xs[1], ki_, xs[2],
                                       xs[3], topk, dt, scopes),
        policy=jax.checkpoint_policies.save_only_these_names(KEPT[0]))
    outs, kls = zip(*[jax.lax.map(
        lambda xs, end=end: body(k[:, :end], v[:, :end], ki[:, :end], xs),
        (chunked(q, lo), chunked(qi, lo), chunked(wi, lo), rows))
        for lo, end, rows in spans])
    return checkpoint_name(joined(outs), KEPT[1]), jnp.mean(joined(kls))


def selected_pairs(T: int, topk: int) -> int:
    """Query-key pairs a ``T``-token row selects: ``sum_t min(t + 1,
    topk)``."""
    full = min(T, topk)
    return full * (full + 1) // 2 + (T - full) * topk


def selected_share(T: int, topk: int) -> float:
    """Selected over causal pairs of a ``T``-token row, from shapes."""
    return selected_pairs(T, topk) / (T * (T + 1) // 2)
