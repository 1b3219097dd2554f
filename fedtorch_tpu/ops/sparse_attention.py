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
lives. Two forms of one algorithm, chosen by what the trace can see
(``takes_kernel``: the backend is a TPU and the shapes tile; no flag,
no mode):

* **The fused form** (``ops/pallas/selected_attention.py``, since PR
  40): the softmax over a query's selected keys, the two products
  around it and the heads' summed probabilities run in kernels that
  keep a ``[queries, keys]`` tile of scores in VMEM; the mask is made
  in the kernel from the indexer's scores, each query's threshold and
  position, and key tiles above the diagonal are skipped, so every
  chunk is given all the row's keys: one call site a kernel and layer
  (a call site a band took 20 s more of every set-up to trace and
  lower). The indexer's products and search, which are ``jax.numpy``
  and skip nothing, keep the bands of key length below in passes of
  their own (over all the keys they cost 0.045 s a round more on the
  chip, PERF.md section 6, PR 40). The layer has ONE backward rule
  (``_fused``, a ``jax.custom_vjp``): a chunk at a time the target's
  sweep again, the attention's backward kernel and the KL term's
  backward, then the indexer's. Its residuals are the inputs and, of
  the forward pass, the output, the log-sum-exp, the thresholds and the
  indexer's scores (``[chunk, keys]`` float32 a chunk, 67 MB a layer
  call at 4096 tokens: 1/32 of the heads' scores), all four under ONE
  name (``KEPT[1]``) for a rematerialized layer's policy: kept, no
  sweep of the forward pass and nothing of the indexer's search runs
  again.
* **The masked dense form** (the CPU's, and the oracle the kernels are
  tested against): a chunk's scores are ``[heads, chunk, keys]``,
  unselected pairs are computed and masked. The chunks run in a few
  *bands* (``KEY_BANDS``): a band's chunks see the keys up to the
  band's last query and no later one, which a causal mask would remove
  anyway, so the dense products do 5/8 of the full square's work at
  four bands (and the indexer's search nothing in bands of no more
  than ``topk`` keys). Each chunk's body is under
  ``jax.checkpoint``: of a chunk only its inputs and each query's
  threshold (``KEPT[0]``) outlive it, and the backward pass runs the
  rest again, not the threshold's search. The output carries
  ``KEPT[1]``: a layer that keeps it runs the chunks' attention twice
  (forward; again inside the backward pass) and not three times.

**A threshold and the scores it is compared with must come from one
compiled pass.** The topk-th score equals its threshold exactly, so a
score made again by another program (a rematerialized layer's backward
pass fuses and orders the indexer's sums differently) and compared with
a KEPT threshold drops or adds the keys at the threshold: 24-29 % off on
a leaf at the tests' eight keys a query, silently (PR 39 read this as a
fault of nested checkpoints in jax 0.9.0; PR 40 found the cause when the
fused form's kept thresholds failed the same way until the scores were
kept beside them). Hence the one name above, and why the dense form's
layer keeps the output alone and searches again: whatever a policy
keeps, it keeps or makes again threshold and scores together
(``tests/test_selected_attention_kernel.py``, the reference under
``remat``).

Types: operands of the products (indexer scores, ``q k^T``, ``p v`` and
the backward kernel's four) in the caller's compute type, accumulated
in float32; the weighted sum over the indexer's heads, the threshold,
the softmax and the KL term in float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from fedtorch_tpu.ops import attention_dispatch

# bands of the dense form's query chunks that share a key length (module
# docstring)
KEY_BANDS = 4
# the names of what need not be computed again: each query's threshold
# (a float; kept by a dense chunk's own checkpoint and never by the
# layer's) and, for a rematerialized layer's policy, the attention's
# output (the fused form: all that its backward rule reads of the
# forward pass)
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


def threshold(scores, rows, topk: int):
    """Each query's threshold [B, C] from a chunk's indexer ``scores``
    [B, C, S]: the ``topk``-th largest score among its causal positions
    (key ``s`` <= the query's position, ``rows`` [C]); ``-inf``, and no
    search, where the keys in sight (of the chunk's last query, if not
    all ``S``) are no more than ``topk``."""
    S = scores.shape[-1]
    everything = lambda: jnp.full(scores.shape[:-1], -jnp.inf, jnp.float32)
    # lint: disable=FTL005 — static lengths
    if S <= topk:
        return everything()

    def search():
        masked = jnp.where(_causal(S, rows)[None], scores, -jnp.inf)
        return kth_largest(jax.lax.stop_gradient(masked), topk)

    return checkpoint_name(
        jax.lax.cond(jnp.max(rows) < topk, everything, search), KEPT[0])


def _causal(S: int, rows):
    return jnp.arange(S)[None, :] <= rows[:, None]


def _selected(scores, rows, kth):
    """The mask [B, C, S] of a chunk from its thresholds: the causal
    positions whose score reaches the query's threshold."""
    return _causal(scores.shape[-1], rows)[None] & (
        scores >= kth[..., None])


def select(scores, rows, topk: int):
    """The selection's mask [B, C, S] of a chunk from its indexer
    ``scores`` [B, C, S]: the causal positions (key ``s`` <= the
    query's position, ``rows`` [C]) whose score is among the row's
    ``topk`` largest; all of them where the keys in sight are no more
    than ``topk``. Scores that tie with the ``topk``-th are all taken."""
    return _selected(scores, rows, threshold(scores, rows, topk))


def _kl(scores, sel, target):
    """The KL term of each query [B, C]: ``target`` [B, C, S] (of mass
    1 over the selection ``sel``) against the softmax of the indexer's
    ``scores`` over it."""
    log_q = jax.nn.log_softmax(jnp.where(sel, scores, -jnp.inf), axis=-1)
    # 0 log 0 = 0, and outside the selection log_q is -inf beside a
    # target of 0: both left out by the two guards
    log_p = jnp.log(jnp.where(target > 0, target, 1.0))
    return jnp.sum(jnp.where(sel, target * (log_p - jnp.where(
        sel, log_q, 0.0)), 0.0), axis=-1)


def _chunk(q, k, v, qi, ki, wi, rows, topk: int, dt, scopes):
    """One chunk of query rows, the masked dense form: ``q``
    [B, C, H, hd], ``k`` / ``v`` [B, S, KV, hd], ``qi`` [B, C, J, di],
    ``ki`` [B, S, di], ``wi`` [B, C, J], ``rows`` [C] the queries'
    positions -> (``o`` [B, C, H, hd] float32, the KL term of each
    query [B, C])."""
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
        kl = _kl(scores, sel, target)
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


def _bands(T: int, C: int):
    """``[(first chunk, chunks, key length)]`` of the bands of a
    ``T``-token row in chunks of ``C`` (module docstring)."""
    n = T // C
    bands = math.gcd(n, KEY_BANDS)
    per = n // bands
    return [(band * per, per, (band + 1) * per * C)
            for band in range(bands)]


def _chunked(t, lo: int, per: int, C: int):
    """``per`` chunks of ``t`` [B, T, ...] from chunk ``lo`` on, the
    chunks first: [per, B, C, ...]."""
    return jnp.moveaxis(t[:, lo * C:(lo + per) * C].reshape(
        (t.shape[0], per, C) + t.shape[2:]), 1, 0)


def _joined(parts):
    """The bands' chunks [per, B, C, ...] back as one [B, T, ...]."""
    return jnp.concatenate(
        [jnp.moveaxis(t, 0, 1).reshape(
            (t.shape[1], t.shape[0] * t.shape[2]) + t.shape[3:])
         for t in parts], axis=1)


def _chunk_inputs(tensors, lo: int, per: int, C: int):
    """What a band's chunks are mapped over: their slices of each of
    ``tensors`` [B, T, ...] and their queries' positions [per, C]."""
    return tuple(_chunked(t, lo, per, C) for t in tensors) + (
        (lo * C + jnp.arange(per * C)).reshape(per, C),)


def takes_kernel(H: int, KV: int, hd: int, C: int, T: int) -> bool:
    """Whether a ``T``-token row in chunks of ``C`` runs the fused
    kernels (``ops/pallas/selected_attention.py``): the backend is a TPU
    and the shapes tile. Decided from what the trace can see; nothing
    chooses it."""
    return attention_dispatch.on_tpu() and _kernels().tiles(
        H, KV, hd, C, T) is not None


def _kernels():
    """The kernels' module, imported by who runs them alone: the dense
    form needs no Pallas (``ops/attention_dispatch.py`` says why)."""
    from fedtorch_tpu.ops.pallas import selected_attention
    return selected_attention


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
    B, T, H, hd = q.shape
    C = chunk_of(T, chunk)
    # lint: disable=FTL005 — the backend and static shapes
    if takes_kernel(H, k.shape[2], hd, C, T):
        out, kls = _fused(q, k, v, qi, ki, wi, topk, C, jnp.dtype(dt),
                          tuple(scopes))
        return out, jnp.mean(kls)
    body = jax.checkpoint(
        lambda k_, v_, ki_, xs: _chunk(xs[0], k_, v_, xs[1], ki_, xs[2],
                                       xs[3], topk, dt, scopes),
        policy=jax.checkpoint_policies.save_only_these_names(KEPT[0]))
    outs, kls = zip(*[jax.lax.map(
        lambda xs, end=end: body(k[:, :end], v[:, :end], ki[:, :end], xs),
        _chunk_inputs((q, qi, wi), lo, per, C))
        for lo, per, end in _bands(T, C)])
    return checkpoint_name(_joined(outs), KEPT[1]), jnp.mean(_joined(kls))


# -- the fused form: one backward rule for the layer's attention ------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _fused(q, k, v, qi, ki, wi, topk, C, dt, scopes):
    """:func:`selected_attention` by the kernels: (``o``, the KL term
    of each query [B, T])."""
    return _fused_fwd(q, k, v, qi, ki, wi, topk, C, dt, scopes)[0]


def _index_scores_in(dt):
    return lambda qi, ki, wi: index_scores(qi.astype(dt), ki.astype(dt), wi)


def _fused_fwd(q, k, v, qi, ki, wi, topk, C, dt, scopes):
    B, T = q.shape[:2]

    def indexer(ki_, xs):
        qi_c, wi_c, rows = xs
        with jax.named_scope(scopes[0]):
            scores = _index_scores_in(dt)(qi_c, ki_, wi_c)
            # past the band's keys lie none that its queries see
            return (jnp.pad(scores, ((0, 0), (0, 0), (0, T - ki_.shape[1]))),
                    threshold(scores, rows, topk))

    def attention(xs):
        q_c, rows, scores, kth = xs
        with jax.named_scope(scopes[1]):
            o, lse = _kernels().forward(q_c, k, v, scores, kth, rows, dt)
            target = _kernels().summed_probabilities(
                q_c, k, lse, scores, kth, rows, dt)
        with jax.named_scope(scopes[0]):
            kl = _kl(scores, _selected(scores, rows, kth), target)
        return o, jnp.moveaxis(lse, 1, 2), kl

    # the indexer's products and search in the bands of key length, the
    # kernels over all the row's keys (module docstring)
    scores, kth = (jnp.concatenate(parts) for parts in zip(*[
        jax.lax.map(functools.partial(indexer, ki[:, :end]),
                    _chunk_inputs((qi, wi), lo, per, C))
        for lo, per, end in _bands(T, C)]))
    o, lse, kl = (_joined([t]) for t in jax.lax.map(
        attention, _chunk_inputs((q,), 0, T // C, C) + (scores, kth)))
    # what the backward rule reads of the forward pass, under ONE name
    # for a rematerialized layer's policy: kept together or made again
    # together, never a threshold of one program beside the scores of
    # another (module docstring)
    o, lse, kth, scores = (checkpoint_name(t, KEPT[1])
                           for t in (o, lse, kth, scores))
    return (o, kl), (q, k, v, qi, ki, wi, o, lse, kth, scores)


def _fused_bwd(topk, C, dt, scopes, res, cotangents):
    """A chunk at a time: the target's sweep again, the attention's one
    backward kernel and the KL term's backward; then, in the bands, the
    indexer's (its products again: the one thing of the indexer that
    runs twice). The mask is the forward pass's own, from its scores
    and thresholds; ``k``, ``v`` and ``ki`` collect their chunks'
    cotangents in float32."""
    q, k, v, qi, ki, wi, o, lse, kth, scores = res
    T = q.shape[1]

    def attention(sums, xs):
        q_c, rows, o_c, lse_c, do_c, dkl_c, scores_c, kth_c = xs
        lse_c = jnp.moveaxis(lse_c, 2, 1)
        with jax.named_scope(scopes[1]):
            target = _kernels().summed_probabilities(
                q_c, k, lse_c, scores_c, kth_c, rows, dt)
            dq, dk, dv = _kernels().backward(
                q_c, k, v, scores_c, kth_c, rows, lse_c, o_c, do_c, dt)
        with jax.named_scope(scopes[0]):
            sel = _selected(scores_c, rows, kth_c)
            dscores, = jax.vjp(lambda s: _kl(s, sel, target), scores_c)[1](
                dkl_c)
        return jax.tree.map(jnp.add, sums, (dk, dv)), (dq, dscores)

    def indexer(ki_, dki, xs):
        qi_c, wi_c, _, dscores = xs
        with jax.named_scope(scopes[0]):
            dqi, dki_c, dwi = jax.vjp(_index_scores_in(dt), qi_c, ki_,
                                      wi_c)[1](dscores[..., :ki_.shape[1]])
        return dki + dki_c, (dqi, dwi)

    (dk, dv), (dq, dscores) = jax.lax.scan(
        attention, tuple(jnp.zeros(t.shape, jnp.float32) for t in (k, v)),
        _chunk_inputs((q,), 0, T // C, C)
        + tuple(_chunked(t, 0, T // C, C) for t in (o, lse) + cotangents)
        + (scores, kth))
    dki, parts = jnp.zeros(ki.shape, jnp.float32), []
    for lo, per, end in _bands(T, C):
        band, chunks = jax.lax.scan(
            functools.partial(indexer, ki[:, :end]), dki[:, :end],
            _chunk_inputs((qi, wi), lo, per, C) + (dscores[lo:lo + per],))
        # the band's sum is the total so far over its keys
        dki = dki.at[:, :end].set(band)
        parts.append(chunks)
    dqi, dwi = (_joined(t) for t in zip(*parts))
    return _joined([dq]), dk, dv, dqi, dki, dwi


_fused.defvjp(_fused_fwd, _fused_bwd)


def selected_pairs(T: int, topk: int) -> int:
    """Query-key pairs a ``T``-token row selects: ``sum_t min(t + 1,
    topk)``."""
    full = min(T, topk)
    return full * (full + 1) // 2 + (T - full) * topk


def selected_share(T: int, topk: int) -> float:
    """Selected over causal pairs of a ``T``-token row, from shapes."""
    return selected_pairs(T, topk) / (T * (T + 1) // 2)
