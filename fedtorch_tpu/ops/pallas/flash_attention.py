"""Pallas TPU kernels: fused flash attention, forward and backward,
under one custom VJP.

The transformer's dense attention (models/transformer.py _SelfAttention)
materializes the full [B, H, T, T] score matrix in HBM — O(T^2) memory
and three HBM sweeps (scores, softmax, combine). The forward kernel
computes exact attention with the online-softmax recurrence (Rabe &
Staats arXiv:2112.05682; FlashAttention arXiv:2205.14135): each
(batch·head, q-block) grid cell streams K/V blocks through VMEM, keeping
running (max, sum, accumulator) statistics, so score memory is one
[block_q, block_k] tile and the output gets ONE HBM write.

The backward pass recomputes a tile's probabilities from the saved
logsumexp — the standard flash VJP — in a kernel of its own (PR 42):
each (batch·head, k-block) streams the q-blocks through VMEM, ``dk`` and
``dv`` of the key block and ``dq`` of the whole sequence add up in
float32 scratch and are written once, so no ``[.., block, T]`` array of
scores exists in either direction. One rule serves both public
functions: the log-sum-exp's cotangent (ring attention's merge) enters
through ``delta``.

Causal mode, both kernels: tiles wholly above the diagonal run no step
and are not fetched (``pl.when`` plus an index map that names the
neighbouring tile again), so the causal pass does ~half the FLOPs; the
mask is made on the tiles the diagonal crosses and nowhere else.

Types, both kernels: the operands of every product in the arrays' own
type (bfloat16 reaches the MXU as bfloat16, float32 as float32: the
input's dtype decides, nothing else; ``p`` and ``ds`` are cast to it as
the dense form casts its probabilities); scores, exponentials, running
statistics, ``delta`` and every accumulator float32.

Who takes which path (``_prep``: ``use_pallas``): on a TPU, where the
blocks tile, both kernels compiled; ``force='interpret'`` both in the
interpreter (the CPU's kernel tests); off a TPU, under ``force='xla'``
and at a degenerate divisor the dense oracle ``_fwd_xla`` with the
chunked scan ``_bwd_chunked`` (a ``lax.scan`` over q-blocks in plain
XLA, float32: O(T·block) live memory, the gradients' reference). A
sequence whose ``dq`` does not fit the backward kernel's VMEM
(``_dq_fits``) keeps the scan behind the forward kernel.

Layout note: q/k/v arrive [B, T, H, D] (the repo's sequence-parallel
layout, parallel/sequence.py) and are re-laid-out to [B·H, T, D] so the
grid's leading axis enumerates independent attention problems.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# Mosaic requires the last two dims of every block to be divisible by
# (8, 128) or equal to the array dims (jax/_src/pallas/mosaic/
# lowering.py:_check_block_mappings: a [1, block_q] lse block is
# REJECTED on-chip even though the interpreter accepts it). Per-q-row
# statistics in VMEM SCRATCH therefore carry a broadcast 128-lane
# trailing dim, the same layout production TPU flash kernels use; lane
# 0 is the value.
_LANES = 128

# The lse HBM OUTPUT does not need the full broadcast: a [BH, T, 8]
# array with a (1, block_q, 8) block also satisfies the rule (last
# block dim EQUALS the array dim; block_q is a divisor block, >= 16 or
# == T, so the sublane constraint holds) and Mosaic accepts the
# lowering (pinned by the AOT-lowering tests in
# tests/test_flash_attention.py). At 8 lanes the lse write is T*8*4
# bytes per head — 16x less HBM traffic than the 128-lane broadcast
# (at D=64/bf16 the broadcast lse write was ~4x the size of the o
# output itself).
_LSE_LANES = 8

# dispatch policy ('auto' backend selection) lives in the pallas-free
# ops/attention_dispatch.py so the dense path never imports this
# module; re-exported here for kernel-side callers
from fedtorch_tpu.ops.attention_dispatch import (  # noqa: E402,F401
    FLASH_MIN_SEQ_LEN, resolve_attention,
)


def _on_tiles(step, causal: bool, qi, kb, blk_q: int, blk_k: int):
    """Run ``step(masked)`` on a tile: everywhere without a mask when
    not ``causal``; else not at all above the diagonal (the skipped
    FLOPs: about half the grid), masked on the tiles the diagonal
    crosses and unmasked below them."""
    if not causal:
        step(False)
        return
    runs = kb * blk_k <= (qi + 1) * blk_q - 1    # some pair is visible
    full = (kb + 1) * blk_k - 1 <= qi * blk_q    # every pair is
    pl.when(runs & full)(lambda: step(False))
    pl.when(runs & jnp.logical_not(full))(lambda: step(True))


def _dot(a, b, contract):
    """A product on the MXU over the ``contract`` dimensions of ``a``
    and ``b``, operands in their own type, float32 out. The ambient
    matmul precision (a test's 'highest') is float32 operands'
    alone: a bfloat16 product is exact in one pass, and Mosaic refuses
    another precision on it ("Bad lhs type")."""
    return jax.lax.dot_general(
        a, b, (contract, ((), ())),
        precision=None if a.dtype == jnp.float32
        else jax.lax.Precision.DEFAULT,
        preferred_element_type=jnp.float32)


def _scores(q, k_blk, scale: float, masked: bool, qi, kb,
            q_axis: int = 0):
    """``q k^T * scale`` of a tile [blk_q, blk_k], or with ``q_axis`` 1
    its transpose ``k q^T * scale`` [blk_k, blk_q]: float32 from
    operands of the arrays' own type (bfloat16 to the MXU as bfloat16);
    ``-inf`` above the diagonal where the tile is ``masked``."""
    rows, cols = ((q, k_blk), (k_blk, q))[q_axis]
    s = _dot(rows, cols, ((1,), (1,))) * scale
    # lint: disable=FTL005 — masked is a static flag of the tile's kind
    if masked:
        q_pos = qi * q.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, q_axis)
        k_pos = kb * k_blk.shape[0] + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1 - q_axis)
        s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
    return s


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale: float, causal: bool):
    """One (batch·head, q-block, k-block) grid cell. The k axis is the
    innermost ('arbitrary') grid dimension: running (max, sum, acc)
    stats live in VMEM scratch across its iterations, so only ONE
    [block_k, D] K/V tile is resident at a time — true streaming, no
    full-sequence VMEM residency. m/l scratch and the lse output are
    [blk_q, 128] lane-broadcast (every lane equal; see _LANES).

    Every row's running maximum is finite from its first tile on (key
    block 0 comes first and holds a visible key of every row, causal or
    not), so ``exp`` of a masked score or of the initial ``-inf``
    maximum less it is a plain 0 and no step needs a guard."""
    qi = pl.program_id(1)
    kb = pl.program_id(2)
    nk = pl.num_programs(2)
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]

    @pl.when(kb == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def update(masked):
        v_blk = v_ref[0]                                 # [blk_k, Dv]
        s = _scores(q_ref[0], k_ref[0], scale, masked, qi, kb)
        m = m_scr[:]                                     # [blk_q, 128]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new[:, :1])                    # [blk_q, blk_k]
        corr = jnp.exp(m - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr[:, :1] + _dot(
            p.astype(v_blk.dtype), v_blk, ((1,), (0,)))

    _on_tiles(update, causal, qi, kb, blk_q, blk_k)

    @pl.when(kb == nk - 1)
    def _():
        l = l_scr[:]            # >= 1: the row's maximum counts as 1
        o_ref[0] = (acc_scr[:] / l[:, :1]).astype(o_ref.dtype)
        # scratch stays 128-lane; only the first _LSE_LANES lanes hit
        # HBM (every lane equal — lane 0 is the value)
        lse_ref[0] = (m_scr[:] + jnp.log(l))[:, :lse_ref.shape[-1]]


def _vma(*arrays):
    """Under shard_map (ring/ulysses call this per shard), jax's vma
    check requires pallas_call outputs to declare which mesh axes they
    vary over — propagate the inputs' vma, even when EMPTY (replicated
    q/k/v inside shard_map still need an explicit one)."""
    return frozenset().union(*(jax.typeof(t).vma for t in arrays))


def _block(shape, index):
    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _fwd_pallas(q3, k3, v3, scale: float, causal: bool, block_q: int,
                block_k: int, interpret: bool):
    """q3, k3 [BH, T, D], v3 [BH, T, Dv] forward -> (o [BH, T, Dv],
    lse [BH, T] f32). ``Dv`` may differ from ``D`` (latent attention:
    query/key heads of 192 beside value heads of 128): the value tile,
    the accumulator and the output are ``Dv`` wide and nothing is
    padded. A head size that is no multiple of 128 lanes is a block's
    whole last dimension, which Mosaic takes."""
    BH, T, D = q3.shape
    Dv = v3.shape[-1]
    grid = (BH, T // block_q, T // block_k)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal)
    vma = _vma(q3, k3, v3)

    def keys(b, i, j):
        # a key block above the query block's last row runs no step:
        # name the last one that does again, which is not fetched twice
        if causal:
            j = jnp.minimum(j, ((i + 1) * block_q - 1) // block_k)
        return b, j, 0

    rows = lambda b, i, j: (b, i, 0)
    o, lse_lanes = pl.pallas_call(
        kernel,
        name="flash_attention_fwd",
        grid=grid,
        in_specs=[_block((1, block_q, D), rows),
                  _block((1, block_k, D), keys),
                  _block((1, block_k, Dv), keys)],
        out_specs=[_block((1, block_q, Dv), rows),
                   _block((1, block_q, _LSE_LANES), rows)],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, Dv), q3.dtype, vma=vma),
            jax.ShapeDtypeStruct((BH, T, _LSE_LANES), jnp.float32,
                                 vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running max
            pltpu.VMEM((block_q, _LANES), jnp.float32),  # running sum
            pltpu.VMEM((block_q, Dv), jnp.float32),      # accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q3, k3, v3)
    return o, lse_lanes[:, :, 0]


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *, scale: float,
                causal: bool):
    """One (batch·head, k-block, q-block) grid cell of the backward
    pass; the q-blocks are the innermost axis, over which a key block's
    ``dk`` and ``dv`` add up in float32 scratch; ``dq`` of the whole
    sequence adds up in float32 scratch over both inner axes and is
    written once. A tile's probabilities come again from the saved
    log-sum-exp, the tile held keys by queries (``s^T = k q^T``): then
    ``dv += p^T do`` and ``dk += ds^T q`` are plain products and only
    ``dq += ds k`` contracts over rows (one transpose a tile where the
    queries-by-keys form has two; 5-12 % of the kernel on a v5e):
    ``p^T = exp(s^T * scale - lse)``, ``dp^T = v do^T``,
    ``ds^T = p^T (dp^T - delta)``, the score's ``scale`` of ``dq`` and
    ``dk`` applied to the float32 sums. ``lse`` and ``delta``
    (``rowsum(do * o)`` less the log-sum-exp's cotangent) arrive as
    ``[1, blk_q]`` rows. Operands of every product in the arrays' own
    type, the rest float32."""
    kb = pl.program_id(1)
    qi = pl.program_id(2)
    nk = pl.num_programs(1)
    nq = pl.num_programs(2)
    blk_q = q_ref.shape[1]
    blk_k = k_ref.shape[1]

    @pl.when((kb == 0) & (qi == 0))
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    @pl.when(qi == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked):
        q, k_blk, v_blk, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        st = _scores(q, k_blk, scale, masked, qi, kb, q_axis=1)
        pt = jnp.exp(st - lse_ref[0, 0])                 # [blk_k, blk_q]
        dpt = _dot(v_blk, do, ((1,), (1,)))
        dst = (pt * (dpt - delta_ref[0, 0])).astype(q.dtype)
        dv_acc[...] += _dot(pt.astype(do.dtype), do, ((1,), (0,)))
        dk_acc[...] += _dot(dst, q, ((1,), (0,)))
        at = pl.ds(pl.multiple_of(qi * blk_q, blk_q), blk_q)
        dq_acc[at, :] += _dot(dst, k_blk, ((0,), (0,)))

    _on_tiles(step, causal, qi, kb, blk_q, blk_k)

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)

    @pl.when((kb == nk - 1) & (qi == nq - 1))
    def _():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


def _bwd_pallas(res, g, g_lse, scale: float, causal: bool, block_q: int,
                block_k: int, interpret: bool):
    """The backward kernel's call: the forward's residuals (q3, k3
    [BH, T, D], v3, o3 [BH, T, Dv], lse [BH, T]), ``o``'s cotangent and
    the log-sum-exp's (None where the caller had no use for it) ->
    (dq, dk, dv) in the inputs' shapes and types."""
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    Dv = v3.shape[-1]
    f32 = jnp.float32
    # D_i = rowsum(do * o), the softmax-jacobian diagonal term; the
    # lse's cotangent enters the score's as ``g_lse * p``, the same
    # shape of term with the other sign
    delta = jnp.sum(g.astype(f32) * o3.astype(f32), axis=-1)
    if g_lse is not None:
        delta = delta - g_lse.astype(f32)

    def seen(j, i):
        # a query block wholly below a key block's first key runs no
        # step: name the first one that does early
        return jnp.maximum(i, (j * block_k) // block_q) if causal else i

    rows = lambda b, j, i: (b, seen(j, i), 0)
    keys = lambda b, j, i: (b, j, 0)
    # a statistic of each row [BH, T] as the kernel reads it: a q-block's
    # are a [1, block_q] row, a block's whole last two dimensions
    # (Mosaic's rule, whatever divisor block_q is)
    stat = _block((1, 1, 1, block_q), lambda b, j, i: (b, seen(j, i), 0, 0))
    as_rows = lambda x: x.reshape(BH, T // block_q, 1, block_q)
    vma = _vma(q3, k3, v3, o3, lse, g, delta)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, causal=causal),
        name="flash_attention_bwd",
        grid=(BH, T // block_k, T // block_q),
        in_specs=[_block((1, block_q, D), rows),
                  _block((1, block_k, D), keys),
                  _block((1, block_k, Dv), keys),
                  _block((1, block_q, Dv), rows),
                  stat, stat],
        out_specs=[_block((1, T, D), lambda b, j, i: (b, 0, 0)),
                   _block((1, block_k, D), keys),
                   _block((1, block_k, Dv), keys)],
        out_shape=[jax.ShapeDtypeStruct(t.shape, t.dtype, vma=vma)
                   for t in (q3, k3, v3)],
        scratch_shapes=[pltpu.VMEM((T, D), f32),
                        pltpu.VMEM((block_k, D), f32),
                        pltpu.VMEM((block_k, Dv), f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_BWD_VMEM_BYTES),
        interpret=interpret,
    )(q3, k3, v3, g, as_rows(lse), as_rows(delta))


def _fwd_xla(q3, k3, v3, scale: float, causal: bool):
    """Dense [BH, T, D] oracle forward returning (o, lse) — identical
    semantics to the kernel, for off-TPU fallback."""
    s = jnp.einsum("bqd,bkd->bqk", q3.astype(jnp.float32),
                   k3.astype(jnp.float32)) * scale
    # lint: disable=FTL005 — causal is a static config flag
    if causal:
        T = q3.shape[1]
        mask = jnp.tril(jnp.ones((T, T), bool))
        s = jnp.where(mask[None], s, -jnp.inf)
    m = jnp.max(s, axis=-1, keepdims=True)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(s - m_safe)
    p = jnp.where(jnp.isfinite(s), p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.maximum(l, 1e-30)
    o = jnp.einsum("bqk,bkd->bqd", p / l_safe, v3.astype(jnp.float32))
    lse = (m_safe + jnp.log(l_safe))[..., 0]
    return o.astype(q3.dtype), lse


def _bwd_chunked(res, g, g_lse=None, *, scale: float, causal: bool,
                 block_q: int):
    """Flash VJP: recompute p blockwise from the saved logsumexp and
    accumulate dk/dv over a q-block scan — O(T·block_q) live memory.
    Pure XLA, float32: the backward pass wherever the forward is the
    dense oracle (off a TPU, ``force='xla'``), and what the backward
    kernel's gradients are held to.

    ``g_lse`` is the logsumexp cotangent (when the caller consumed the
    lse output — the ring-attention merge does): ∂lse/∂s = p, so it
    adds a ``g_lse·p`` term to the score cotangent; lse is independent
    of v."""
    q3, k3, v3, o3, lse = res
    BH, T, D = q3.shape
    f32 = jnp.float32
    q3f, k3f, v3f, o3f, gf = (t.astype(f32) for t in
                              (q3, k3, v3, o3, g))
    glf = jnp.zeros_like(lse) if g_lse is None else g_lse.astype(f32)
    # D_i = rowsum(do * o) — the softmax-jacobian diagonal term
    delta = jnp.sum(gf * o3f, axis=-1)                   # [BH, T]
    nq = T // block_q

    def step(carry, i):
        dk, dv = carry
        sl = jax.lax.dynamic_slice_in_dim
        q_i = sl(q3f, i * block_q, block_q, 1)           # [BH, bq, D]
        g_i = sl(gf, i * block_q, block_q, 1)
        lse_i = sl(lse, i * block_q, block_q, 1)
        d_i = sl(delta, i * block_q, block_q, 1)
        gl_i = sl(glf, i * block_q, block_q, 1)
        s = jnp.einsum("bqd,bkd->bqk", q_i, k3f) * scale
        if causal:
            q_pos = i * block_q + jnp.arange(block_q)
            mask = q_pos[:, None] >= jnp.arange(T)[None]
            s = jnp.where(mask[None], s, -jnp.inf)
        p = jnp.exp(s - lse_i[..., None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)           # [BH, bq, T]
        dv = dv + jnp.einsum("bqk,bqd->bkd", p, g_i)
        dp = jnp.einsum("bqd,bkd->bqk", g_i, v3f)
        ds = p * (dp - d_i[..., None] + gl_i[..., None]) * scale
        dq_i = jnp.einsum("bqk,bkd->bqd", ds, k3f)
        dk = dk + jnp.einsum("bqk,bqd->bkd", ds, q_i)
        return (dk, dv), dq_i

    (dk, dv), dq_blocks = jax.lax.scan(
        step, (jnp.zeros_like(k3f), jnp.zeros_like(v3f)),
        jnp.arange(nq))
    # [nq, BH, bq, D] -> [BH, T, D]
    dq = jnp.moveaxis(dq_blocks, 0, 1).reshape(BH, T, D)
    return (dq.astype(q3.dtype), dk.astype(k3.dtype),
            dv.astype(v3.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3(q3, k3, v3, scale, causal, block_q, block_k, use_pallas):
    out, _ = _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                         use_pallas)
    return out


def _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k, use_pallas):
    # lint: disable=FTL005 — use_pallas is a static backend switch
    if use_pallas is None or use_pallas:
        o, lse = _fwd_pallas(q3, k3, v3, scale, causal, block_q,
                             block_k, interpret=use_pallas is None)
    else:
        o, lse = _fwd_xla(q3, k3, v3, scale, causal)
    return o, (q3, k3, v3, o, lse)


def _backward(res, g, g_lse, scale, causal, block_q, block_k,
              use_pallas):
    """The one backward rule of both public functions: the kernel
    wherever the forward ran its own (compiled or interpreted), the
    chunked scan where it ran the dense oracle."""
    # lint: disable=FTL005 — use_pallas is a static backend switch
    if use_pallas is not False and _dq_fits(*res[0].shape[1:]):
        return _bwd_pallas(res, g, g_lse, scale, causal, block_q,
                           block_k, interpret=use_pallas is None)
    return _bwd_chunked(res, g, g_lse, scale=scale, causal=causal,
                        block_q=block_q)


def _flash3_bwd(scale, causal, block_q, block_k, use_pallas, res, g):
    return _backward(res, g, None, scale, causal, block_q, block_k,
                     use_pallas)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3_lse(q3, k3, v3, scale, causal, block_q, block_k,
                use_pallas):
    """Like _flash3 but also returns the logsumexp [BH, T] — the
    statistic that makes attention outputs MERGEABLE (ring attention
    combines per-block results by lse weighting). Differentiable in
    both outputs (one rule with _flash3: _backward)."""
    out, res = _flash3_lse_fwd(q3, k3, v3, scale, causal, block_q,
                               block_k, use_pallas)
    return out


def _flash3_lse_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                    use_pallas):
    # one backend-dispatch implementation: _flash3_fwd's residuals
    # already carry the lse, so the lse-returning variant just
    # surfaces it — the two public kernels cannot diverge
    out, res = _flash3_fwd(q3, k3, v3, scale, causal, block_q, block_k,
                           use_pallas)
    return (out, res[4]), res


def _flash3_lse_bwd(scale, causal, block_q, block_k, use_pallas, res,
                    g):
    g_o, g_lse = g
    return _backward(res, g_o, g_lse, scale, causal, block_q, block_k,
                     use_pallas)


_flash3_lse.defvjp(_flash3_lse_fwd, _flash3_lse_bwd)


# Largest block_q*block_k score tile the kernels may hold in VMEM: the
# default's from T = 4096 (4 MB float32 a tile; compiled and run on a
# v5e at float32 and bfloat16 operands, PR 42). Only an explicit request
# or the degenerate-divisor path can exceed it.
_MAX_BLOCK_ELEMS = 1024 * 1024

# The backward kernel's scoped VMEM, of a v5e's 128 MiB: at (1024, 1024)
# some five float32 score tiles (4 MB each) and the operand tiles beside
# the whole sequence's ``dq``, which it holds as a float32 sum and as
# the double-buffered output block: at most half of this (a sequence of
# 4096 at heads of 192, bfloat16: 8 MB).
_BWD_VMEM_BYTES = 64 * 2 ** 20


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _dq_fits(T: int, D: int) -> bool:
    """Whether a sequence's ``dq`` fits the backward kernel's VMEM
    beside its tiles (lanes padded to 128, counted at float32: up to
    T = 8192 at heads of 256, 16384 at 128). Beyond, the chunked scan
    stays behind the forward kernel."""
    lanes = -(-D // _LANES) * _LANES
    return T * lanes * 12 <= _BWD_VMEM_BYTES // 2


def _divisor_block(T: int, block: int) -> int:
    """Largest usable block size that DIVIDES T (<= the request).

    Every code path — kernel grid, backward scan — assumes
    ``T % block == 0``; deriving the block here makes that a structural
    invariant instead of a fallback condition. Degenerate divisors
    (< 16 rows) would make the scan/grid long and thin, so those round
    up to T (one block — still exact, standard memory)."""
    if T <= block:
        return T
    if T % block == 0:
        return block
    d = math.gcd(T, block)
    return d if d >= 16 else T


def _default_blocks(T: int):
    """Default block shape by sequence length, both kernels': (1024,
    1024) from T = 4096, (128, 128) below.

    Read on a v5e at the latent cell's shapes (32 heads, 192 / 128
    wide, T = 4096, bfloat16, causal; builder, PR 42, 2026-10-03,
    PERF.md section 6), ms a call forward / backward: (256, 256) 6.16 /
    6.31; (512, 256) 5.23 / 5.29; (256, 512) 3.92 / 5.28; (512, 512)
    3.36 / 4.65; (1024, 512) 3.67 / 4.70; (512, 1024) 2.59 / 4.71;
    (1024, 1024) 2.38 / 4.55; (512, 2048) 2.74 / 5.26; (1024, 2048)
    2.69 / 5.21. The forward wants long key blocks (its per-step work
    on the running statistics and the accumulator's rescaling is by
    query row, whatever the keys) until the diagonal's half-empty
    tiles cost more, the backward is flat from (512, 512) on; at 30
    heads of 128 the same order (forward 2.33 at (512, 512), 1.42 at
    (1024, 1024)), and at 8 heads of 64, T = 8192 (2.09, 1.28). The sub-4096 default rests on
    a capture of 2026-07-31, before PR 1 (record removed in PR 29) and
    governs explicit ``attention='flash'`` requests only: 'auto'
    dispatch routes T < 4096 to dense (ops/attention_dispatch.py)."""
    return (128, 128) if T <= 2048 else (1024, 1024)


def _prep(q, k, v, scale, block_q, block_k, force):
    """Shared wrapper plumbing: [B,T,H,D] -> [BH,T,D] layout, divisor
    block sizes, backend selection."""
    B, T, H, D = q.shape
    if block_q is None or block_k is None:
        dq, dk = _default_blocks(T)
        block_q = dq if block_q is None else block_q
        block_k = dk if block_k is None else block_k
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        # The kernel grid and chunked VJP tile Q and K/V with one shared
        # T; unequal q/kv lengths (e.g. cross-attention or uneven K/V
        # partitions) are not supported — fail with the shapes rather
        # than an opaque reshape error downstream. Ring/Ulysses always
        # pass equal-size blocks. The VALUE head size is v's own (the
        # output's too): latent attention has query/key heads of 192
        # beside value heads of 128.
        raise ValueError(
            "flash attention requires q and k of identical shape "
            "[B, T, H, D] and v [B, T, H, Dv]; got "
            f"q={q.shape}, k={k.shape}, v={v.shape}. "
            "For disjoint K/V partitions, run the kernel per equal-size "
            "block and merge with the returned logsumexp.")
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    block_q = _divisor_block(T, block_q)
    block_k = _divisor_block(T, block_k)
    q3, k3, v3 = (t.transpose(0, 2, 1, 3).reshape(B * H, T, t.shape[-1])
                  for t in (q, k, v))
    return (q3, k3, v3), (B, T, H, v.shape[-1]), scale, block_q, \
        block_k, _backend(block_q, block_k, force)


def _backend(block_q: int, block_k: int, force):
    """``use_pallas`` of a call with these (divisor) blocks: True the
    compiled kernels, None the interpreted ones, False the dense oracle
    and the chunked scan."""
    if force not in (None, "interpret", "xla"):
        raise ValueError(
            f"unknown force={force!r} (expected None, 'interpret', or "
            "'xla')")
    if force == "interpret":
        return None                 # pallas_call(interpret=True)
    if force == "xla" or not on_tpu():
        return False
    # degenerate divisor (prime-ish T) collapsed to near-T blocks: a
    # [block_q, block_k] f32 score tile would blow VMEM on the real
    # lowering — the XLA oracle is the correct backend there
    return block_q * block_k <= _MAX_BLOCK_ELEMS


def backward_kernel_taken(T: int, D: int) -> bool:
    """Whether a call with the default blocks on ``T``-long rows at
    query heads of ``D`` runs the backward kernel on this backend: the
    decision :func:`_backward` makes as the step is traced."""
    block_q, block_k = (_divisor_block(T, b) for b in _default_blocks(T))
    return _backend(block_q, block_k, None) is not False \
        and _dq_fits(T, D)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    force: Optional[str] = None) -> jnp.ndarray:
    """Exact attention, q and k [B, T, H, D], v and the result
    [B, T, H, Dv] (``Dv`` = ``D`` but for latent attention),
    differentiable.

    Backend selection: the Pallas kernels on TPU, forward and backward;
    their interpreter when ``force='interpret'`` (CPU kernel tests);
    the dense-oracle math and the chunked VJP otherwise (CPU
    training/eval — same semantics, standard memory).
    Block sizes default to the measured per-T winners
    (``_default_blocks``) and are adjusted to divisors of T (static
    shapes: decided once at trace time), so the kernels' grids and
    the chunked VJP always tile the sequence exactly."""
    (q3, k3, v3), (B, T, H, Dv), scale, bq, bk, use_pallas = _prep(
        q, k, v, scale, block_q, block_k, force)
    out3 = _flash3(q3, k3, v3, scale, causal, bq, bk, use_pallas)
    return out3.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None,
                             force: Optional[str] = None):
    """:func:`flash_attention` that also returns the logsumexp
    ([B, T, H] f32) — the merge statistic for combining attention over
    disjoint K/V blocks: pieces (o_i, lse_i) over K-partitions combine
    exactly via lse-weighted averaging (ring attention's per-step
    blocks, parallel/sequence.py). Differentiable in both outputs."""
    (q3, k3, v3), (B, T, H, Dv), scale, bq, bk, use_pallas = _prep(
        q, k, v, scale, block_q, block_k, force)
    o3, lse3 = _flash3_lse(q3, k3, v3, scale, causal, bq, bk,
                           use_pallas)
    o = o3.reshape(B, H, T, Dv).transpose(0, 2, 1, 3)
    return o, lse3.reshape(B, H, T).transpose(0, 2, 1)
