"""Pallas TPU kernels: the attention of a chunk of query rows over the
keys a learned indexer selected (``ops/sparse_attention.py``), with the
scores never written to HBM.

The masked dense form writes a chunk's ``[heads, chunk, keys]`` float32
scores and reads or writes them a dozen times (mask, maximum,
exponential, sum, normalisation, cast, the heads' sum; all of it again
under the chunk's checkpoint, and the softmax's backward). Here a
``[queries, keys]`` tile of scores lives in VMEM alone, in three
kernels:

* :func:`forward`: the online softmax (Rabe & Staats arXiv:2112.05682,
  FlashAttention arXiv:2205.14135) over the key tiles of a query tile
  -> the output and each row's log-sum-exp.
* :func:`backward`, one kernel: the probabilities of a tile again from
  ``q``, ``k`` and the log-sum-exp, ``dv = p^T do``, ``dp = do v^T``,
  ``ds = p (dp - sum(do o))``, ``dq = ds k``, ``dk = ds^T q``; ``dk`` /
  ``dv`` of a key tile add up in VMEM over the query tiles and the
  heads of its group, ``dq`` of the whole chunk stays in VMEM over the
  key tiles.
* :func:`summed_probabilities`: a second sweep, ``q k^T`` again and
  ``exp(s - lse)`` summed over all the heads and divided by their
  number: the indexer's target.

The selection arrives as what defines it: the indexer's scores of the
chunk ``[B, C, S]``, each query's threshold ``kth`` and position
``rows``; a tile's mask is ``key <= row and score >= kth``, made in the
kernel (``sparse_attention.select``'s rules: ties with the threshold
all taken; a threshold of ``-inf`` takes every causal key). The query
heads of one key head share its tiles: a grid cell takes the ``G = H /
KV`` heads of a group as ``G x tile_q`` rows against one ``K`` / ``V``
tile. Key tiles wholly above a query tile's last row are skipped: no
step of the kernel runs there and the tile before is not fetched again.

Types: operands of every product in ``dt`` (the caller's compute type;
bfloat16 to the MXU as bfloat16), accumulated in float32; scores, mask,
maximum, exponential, sums and rescaling in float32.

None of the three is differentiable: ``sparse_attention`` owns the one
backward rule of the layer, which calls them. Off the TPU the same
kernels run in the Pallas interpreter (the tests'); who takes them in
the program is ``sparse_attention``'s decision.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtorch_tpu.ops import attention_dispatch

# what a masked score is set to: finite, so that no step subtracts
# infinities; a real score beside it rounds away, ``exp`` of it less any
# real log-sum-exp is 0, and what a row collects over tiles in which it
# selects nothing is scaled by ``exp(MASKED - a real maximum) = 0`` when
# its first selected key arrives (every row selects its own position or
# ``topk`` others)
MASKED = -1e30
# per-row statistics in VMEM scratch carry a broadcast 128-lane minor
# dimension; in HBM 8 lanes (``ops/pallas/flash_attention.py`` says why
# both lower)
_LANES = 128
_STAT_LANES = 8
# the most rows (a group's heads x a query tile) of a score tile, and
# the kernels' scoped VMEM: the backward kernel holds some four float32
# score tiles of ``_MAX_ROWS`` by 512 keys (8 MB each), the chunk's
# ``dq`` and the double-buffered operand tiles; of a v5e's 128 MiB
_MAX_ROWS = 4096
_VMEM_BYTES = 64 * 2 ** 20


def tiles(H: int, KV: int, hd: int, C: int, S: int
          ) -> Optional[Tuple[int, int]]:
    """``(tile_q, tile_k)`` of a chunk of ``C`` queries on ``S`` keys,
    ``H`` query on ``KV`` key heads of ``hd``; None where the shapes do
    not tile for the MXU (the head size no multiple of its 128 lanes,
    the chunk or the keys no whole tiles, the heads no whole groups).
    The largest query tile whose group's rows are no more than
    ``_MAX_ROWS`` (read on a v5e, PERF.md section 6, PR 40: a chunk of
    512 queries in one tile is a fifth faster forward than in four), by
    512 keys where they divide."""
    if H % KV or hd % _LANES:
        return None
    for tile_q in (512, 256, 128):
        if C % tile_q == 0 and (H // KV) * tile_q <= _MAX_ROWS:
            break
    else:
        return None
    for tile_k in (512, 256, 128):
        if S % tile_k == 0:
            return tile_q, tile_k
    return None


def _heads_as_rows(ref, G: int, hd: int, dt):
    """A ``[1, tile_q, G x hd]`` block of a group's heads -> ``[G x
    tile_q, hd]`` of ``dt``, a head's rows together."""
    return jnp.concatenate(
        [ref[0, :, g * hd:(g + 1) * hd].astype(dt) for g in range(G)],
        axis=0)


def _mask_bias(sc_ref, kth_ref, pos_ref, j):
    """0 where the tile's pair is selected, ``MASKED`` elsewhere:
    ``[tile_q, tile_k]`` float32, for all the heads."""
    sc = sc_ref[0]
    keys = j * sc.shape[1] + jax.lax.broadcasted_iota(jnp.int32, sc.shape,
                                                      1)
    keep = (keys <= pos_ref[...]) & (sc >= kth_ref[0])
    return jnp.where(keep, 0.0, MASKED)


def _masked_scores(qs, kb, bias, G: int, scale: float):
    """``q k^T`` of a group's heads against a key tile, scaled and
    masked: ``[G, tile_q, tile_k]`` float32."""
    s = jax.lax.dot_general(qs, kb, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    return s.reshape((G,) + bias.shape) + bias[None]


def _fwd_kernel(last_ref, q_ref, k_ref, v_ref, sc_ref, kth_ref, pos_ref,
                o_ref, lse_ref, qs, m_scr, l_scr, acc, *, G, hd, scale,
                dt):
    """One (row, key head, query tile, key tile) grid cell; the key
    tiles are the innermost, sequential axis, over which the running
    maximum, sum and accumulator of the group's ``G x tile_q`` rows live
    in VMEM scratch."""
    i, j = pl.program_id(2), pl.program_id(3)
    tq, tk = sc_ref.shape[1:]

    @pl.when(j == 0)
    def _():
        qs[...] = _heads_as_rows(q_ref, G, hd, dt)
        m_scr[...] = jnp.full_like(m_scr, MASKED)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc[...] = jnp.zeros_like(acc)

    @pl.when(j <= last_ref[i])
    def _():
        s = _masked_scores(qs[...], k_ref[0].astype(dt),
                           _mask_bias(sc_ref, kth_ref, pos_ref, j), G,
                           scale).reshape(G * tq, tk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc[...] = acc[...] * corr[:, :1] + jax.lax.dot_general(
            p.astype(dt), v_ref[0].astype(dt), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        out = acc[...] / l[:, :1]
        for g in range(G):
            o_ref[0, :, g * hd:(g + 1) * hd] = out[g * tq:(g + 1) * tq]
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l))[:, :_STAT_LANES].reshape(
            G, tq, _STAT_LANES)


def _target_kernel(last_ref, q_ref, k_ref, lse_ref, sc_ref, kth_ref,
                   pos_ref, t_ref, *, G, hd, scale, dt, heads):
    """One (row, query tile, key tile, key head) grid cell; the key
    heads are the innermost, sequential axis, over which the tile of
    summed probabilities stays in VMEM."""
    i, j, h = pl.program_id(1), pl.program_id(2), pl.program_id(3)

    @pl.when(h == 0)
    def _():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(j <= last_ref[i])
    def _():
        s = _masked_scores(_heads_as_rows(q_ref, G, hd, dt),
                           k_ref[0].astype(dt),
                           _mask_bias(sc_ref, kth_ref, pos_ref, j), G,
                           scale)
        p = jnp.exp(s - lse_ref[0, 0][:, :, :1])
        t_ref[0] += jnp.sum(p, axis=0) * (1.0 / heads)


def _bwd_kernel(last_ref, first_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, sc_ref, kth_ref, pos_ref, dq_ref, dk_ref,
                dv_ref, dk_acc, dv_acc, *, G, hd, scale, dt):
    """One (row, key head, key tile, query tile) grid cell; the query
    tiles are the innermost axis, over which a key tile's ``dk`` and
    ``dv`` add up in scratch (over the group's heads too: they are rows
    of the same products); the chunk's ``dq`` of the group is one
    output block, resident over both inner axes."""
    del first_ref   # the index maps' (which query tile to fetch)
    j, i = pl.program_id(2), pl.program_id(3)
    tq, tk = sc_ref.shape[1:]

    @pl.when((j == 0) & (i == 0))
    def _():
        dq_ref[...] = jnp.zeros_like(dq_ref)

    @pl.when(i == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    @pl.when(j <= last_ref[i])
    def _():
        qs = _heads_as_rows(q_ref, G, hd, dt)
        dos = _heads_as_rows(do_ref, G, hd, dt)
        kb, vb = k_ref[0].astype(dt), v_ref[0].astype(dt)
        s = _masked_scores(qs, kb, _mask_bias(sc_ref, kth_ref, pos_ref, j),
                           G, scale)
        p = jnp.exp(s - lse_ref[0, 0][:, :, :1]).reshape(G * tq, tk)
        dp = jax.lax.dot_general(dos, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = delta_ref[0, 0][:, :, :1].reshape(G * tq, 1)
        ds = (p * (dp - delta) * scale).astype(dt)
        over_rows = (((0,), (0,)), ((), ()))
        dv_acc[...] += jax.lax.dot_general(
            p.astype(dt), dos, over_rows,
            preferred_element_type=jnp.float32)
        dk_acc[...] += jax.lax.dot_general(
            ds, qs, over_rows, preferred_element_type=jnp.float32)
        dq = jax.lax.dot_general(ds, kb, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        at = pl.ds(pl.multiple_of(i * tq, tq), tq)
        for g in range(G):
            dq_ref[0, at, g * hd:(g + 1) * hd] += dq[g * tq:(g + 1) * tq]

    @pl.when(i == pl.num_programs(3) - 1)
    def _():
        dk_ref[0] = dk_acc[...]
        dv_ref[0] = dv_acc[...]


def _shapes(q, k, tile_q, tile_k):
    """The sizes a call works with, the tiles defaulted to
    :func:`tiles`'s: (B, C, H, hd, S, KV, heads a group, tile_q,
    tile_k, query tiles, key tiles)."""
    B, C, H, hd = q.shape
    S, KV = k.shape[1:3]
    if tile_q is None or tile_k is None:
        found = tiles(H, KV, hd, C, S)
        if found is None:
            raise ValueError(
                f"no tiles for {H} query on {KV} key heads of {hd}, {C} "
                f"queries on {S} keys")
        tile_q, tile_k = found
    return (B, C, H, hd, S, KV, H // KV, tile_q, tile_k, C // tile_q,
            S // tile_k)


def _last_tiles(rows, tile_q: int, tile_k: int):
    """[query tiles] int32: the last key tile a query tile sees (the
    one its largest position lies in)."""
    return (jnp.max(rows.reshape(-1, tile_q), axis=1) // tile_k).astype(
        jnp.int32)


def _block(shape, index):
    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _call(kernel, name: str, semantics, **kw):
    """``pl.pallas_call`` as the three kernels make it: compiled on a
    TPU, interpreted elsewhere."""
    return pl.pallas_call(
        kernel, name=name, interpret=not attention_dispatch.on_tpu(),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics, vmem_limit_bytes=_VMEM_BYTES),
        **kw)


def _mask_operands(scores, kth, rows):
    return scores, kth[..., None], rows.astype(jnp.int32)[:, None]


def _mask_specs(tile_q, tile_k, at):
    """The block specifications of :func:`_mask_operands`; ``at`` maps a
    grid cell (and the prefetched scalars) to (row, query tile, key
    tile)."""
    def spec(block, index):
        return _block(block, lambda *g: index(*at(*g)))
    return [spec((1, tile_q, tile_k), lambda b, i, j: (b, i, j)),
            spec((1, tile_q, 1), lambda b, i, j: (b, i, 0)),
            spec((tile_q, 1), lambda b, i, j: (i, 0))]


def _stat_lanes(x, KV: int, G: int):
    """A statistic of each head's rows [B, H, C] as the kernels read
    it: [B, KV, G, C, 8], every lane the value."""
    B, _, C = x.shape
    return jnp.broadcast_to(x.reshape(B, KV, G, C, 1),
                            (B, KV, G, C, _STAT_LANES))


def forward(q, k, v, scores, kth, rows, dt, *, tile_q=None, tile_k=None):
    """``q`` [B, C, H, hd], ``k`` / ``v`` [B, S, KV, hd] (query head
    ``h`` reads key head ``h // (H / KV)``), the indexer's ``scores``
    [B, C, S] float32, each query's threshold ``kth`` [B, C] and
    position ``rows`` [C] -> (``o`` [B, C, H, hd] float32, the
    log-sum-exp over a query's selected keys [B, H, C] float32). The
    tiles default to :func:`tiles`'s (the tests pass small ones)."""
    B, C, H, hd, S, KV, G, tile_q, tile_k, nq, nk = _shapes(
        q, k, tile_q, tile_k)
    dt = jnp.dtype(dt)
    # a key tile past the query tile's last: the last one again, which
    # is not fetched twice
    at = lambda b, h, i, j, last: (b, i, jnp.minimum(j, last[i]))
    heads = _block((1, tile_q, G * hd), lambda b, h, i, j, last: (b, i, h))
    keys = _block((1, tile_k, hd), lambda b, h, i, j, last: (
        b, jnp.minimum(j, last[i]), h))
    o, lse = _call(
        functools.partial(_fwd_kernel, G=G, hd=hd,
                          scale=1.0 / math.sqrt(hd), dt=dt),
        "selected_attention_fwd",
        ("parallel", "parallel", "parallel", "arbitrary"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, KV, nq, nk),
            in_specs=[heads, keys, keys] + _mask_specs(tile_q, tile_k, at),
            out_specs=[heads, _block(
                (1, 1, G, tile_q, _STAT_LANES),
                lambda b, h, i, j, last: (b, h, 0, i, 0))],
            scratch_shapes=[
                pltpu.VMEM((G * tile_q, hd), dt),
                pltpu.VMEM((G * tile_q, _LANES), jnp.float32),
                pltpu.VMEM((G * tile_q, _LANES), jnp.float32),
                pltpu.VMEM((G * tile_q, hd), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, C, H * hd), jnp.float32),
            jax.ShapeDtypeStruct((B, KV, G, C, _STAT_LANES), jnp.float32)],
    )(_last_tiles(rows, tile_q, tile_k), q.reshape(B, C, H * hd),
      k.reshape(B, S, KV * hd), v.reshape(B, S, KV * hd),
      *_mask_operands(scores, kth, rows))
    return o.reshape(q.shape), lse[..., 0].reshape(B, H, C)


def backward(q, k, v, scores, kth, rows, lse, o, do, dt, *, tile_q=None,
             tile_k=None):
    """:func:`forward`'s arguments, its two results and ``o``'s
    cotangent ``do`` [B, C, H, hd] -> the cotangents of ``q``, ``k``,
    ``v`` in their shapes, float32. The mask passes no gradient."""
    B, C, H, hd, S, KV, G, tile_q, tile_k, nq, nk = _shapes(
        q, k, tile_q, tile_k)
    dt = jnp.dtype(dt)
    lse = _stat_lanes(lse, KV, G)
    last = _last_tiles(rows, tile_q, tile_k)
    # the first query tile that sees a key tile: those before it run no
    # step, and fetch that one early
    first = jnp.argmax(last[None, :] >= jnp.arange(nk)[:, None],
                       axis=1).astype(jnp.int32)
    seen = lambda i, j, first: jnp.maximum(i, first[j])
    at = lambda b, h, j, i, last, first: (b, seen(i, j, first), j)
    heads = _block((1, tile_q, G * hd), lambda b, h, j, i, last, first: (
        b, seen(i, j, first), h))
    keys = _block((1, tile_k, hd), lambda b, h, j, i, last, first: (b, j, h))
    stats = _block((1, 1, G, tile_q, _STAT_LANES),
                  lambda b, h, j, i, last, first: (
                      b, h, 0, seen(i, j, first), 0))
    delta = _stat_lanes(jnp.moveaxis(jnp.sum(do * o, axis=-1), 1, 2), KV, G)
    dq, dk, dv = _call(
        functools.partial(_bwd_kernel, G=G, hd=hd,
                          scale=1.0 / math.sqrt(hd), dt=dt),
        "selected_attention_bwd",
        ("parallel", "parallel", "arbitrary", "arbitrary"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(B, KV, nk, nq),
            in_specs=[heads, keys, keys, heads, stats, stats]
            + _mask_specs(tile_q, tile_k, at),
            out_specs=[
                _block((1, C, G * hd),
                      lambda b, h, j, i, last, first: (b, 0, h)),
                keys, keys],
            scratch_shapes=[pltpu.VMEM((tile_k, hd), jnp.float32),
                            pltpu.VMEM((tile_k, hd), jnp.float32)]),
        out_shape=[
            jax.ShapeDtypeStruct((B, C, H * hd), jnp.float32),
            jax.ShapeDtypeStruct((B, S, KV * hd), jnp.float32),
            jax.ShapeDtypeStruct((B, S, KV * hd), jnp.float32)],
    )(last, first, q.reshape(B, C, H * hd), k.reshape(B, S, KV * hd),
      v.reshape(B, S, KV * hd), do.reshape(B, C, H * hd), lse, delta,
      *_mask_operands(scores, kth, rows))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


def summed_probabilities(q, k, lse, scores, kth, rows, dt, *, tile_q=None,
                         tile_k=None):
    """The heads' probabilities over the selected keys, summed and
    divided by their number: ``q`` [B, C, H, hd], ``k`` [B, S, KV, hd],
    :func:`forward`'s ``lse`` [B, H, C] and the mask's three ->
    [B, C, S] float32 of mass 1 a row."""
    B, C, H, hd, S, KV, G, tile_q, tile_k, nq, nk = _shapes(
        q, k, tile_q, tile_k)
    dt = jnp.dtype(dt)
    at = lambda b, i, j, h, last: (b, i, jnp.minimum(j, last[i]))
    return _call(
        functools.partial(_target_kernel, G=G, hd=hd,
                          scale=1.0 / math.sqrt(hd), dt=dt, heads=H),
        "selected_attention_target",
        ("parallel", "parallel", "parallel", "arbitrary"),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, nq, nk, KV),
            in_specs=[
                _block((1, tile_q, G * hd),
                      lambda b, i, j, h, last: (b, i, h)),
                _block((1, tile_k, hd), lambda b, i, j, h, last: (
                    b, jnp.minimum(j, last[i]), h)),
                _block((1, 1, G, tile_q, _STAT_LANES),
                      lambda b, i, j, h, last: (b, h, 0, i, 0))]
            + _mask_specs(tile_q, tile_k, at),
            out_specs=_block((1, tile_q, tile_k),
                            lambda b, i, j, h, last: (b, i, j))),
        out_shape=jax.ShapeDtypeStruct((B, C, S), jnp.float32),
    )(_last_tiles(rows, tile_q, tile_k), q.reshape(B, C, H * hd),
      k.reshape(B, S, KV * hd),
      _stat_lanes(lse, KV, G),
      *_mask_operands(scores, kth, rows))
