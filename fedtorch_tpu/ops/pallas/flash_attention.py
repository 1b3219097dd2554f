"""Pallas TPU kernel: fused flash attention (forward) + memory-efficient
custom VJP.

The transformer's dense attention (models/transformer.py _SelfAttention)
materializes the full [B, H, T, T] score matrix in HBM — O(T^2) memory
and three HBM sweeps (scores, softmax, combine). This kernel computes
exact attention with the online-softmax recurrence (Rabe & Staats
arXiv:2112.05682; FlashAttention arXiv:2205.14135): each (batch·head,
q-block) grid cell streams K/V blocks through VMEM, keeping running
(max, sum, accumulator) statistics, so score memory is one
[block_q, block_k] tile and the output gets ONE HBM write. Causal mode
skips fully-masked K blocks outright (the loop bound, not a mask, so the
causal forward does ~half the FLOPs).

The backward pass recomputes probabilities blockwise from the saved
logsumexp — the standard flash VJP — as a `lax.scan` over q-blocks in
plain XLA: O(T·block) live memory, no T^2 tensor, and exact gradients
(tests pin both against the dense oracle).

Off-TPU (CPU tests) `flash_attention` uses the same math via the
interpreter (``force='interpret'``) or the dense oracle; on TPU the
Mosaic kernel is the only path short of an explicit ``force='xla'``.

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


def _kernel_finite(x):
    """``jnp.isfinite`` spelled as a comparison: NaN and +/-inf both
    compare False under ``abs(x) < inf``, which lowers under Mosaic
    and in the interpreter with identical semantics."""
    return jnp.abs(x) < jnp.inf


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr,
                acc_scr, *, scale: float, causal: bool):
    """One (batch·head, q-block, k-block) grid cell. The k axis is the
    innermost ('arbitrary') grid dimension: running (max, sum, acc)
    stats live in VMEM scratch across its iterations, so only ONE
    [block_k, D] K/V tile is resident at a time — true streaming, no
    full-sequence VMEM residency. m/l scratch and the lse output are
    [blk_q, 128] lane-broadcast (every lane equal; see _LANES)."""
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

    def update():
        q = q_ref[0].astype(jnp.float32)                 # [blk_q, D]
        k_blk = k_ref[0].astype(jnp.float32)             # [blk_k, D]
        v_blk = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [blk_q, blk_k]
        if causal:
            q_pos = qi * blk_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0)
            k_pos = kb * blk_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, -jnp.inf)
        m = m_scr[:]                                     # [blk_q, 128]
        m_blk = jnp.max(s, axis=-1, keepdims=True)       # [blk_q, 1]
        m_new = jnp.maximum(m, m_blk)                    # [blk_q, 128]
        m_safe = jnp.where(_kernel_finite(m_new), m_new, 0.0)
        p = jnp.exp(s - m_safe[:, :1])
        p = jnp.where(_kernel_finite(s), p, 0.0)           # [blk_q, blk_k]
        corr = jnp.where(_kernel_finite(m), jnp.exp(m - m_safe), 0.0)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr[:, :1] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        # K blocks entirely past this q-block's last position contribute
        # nothing — skip their FLOPs outright (~half the grid)
        pl.when(kb * blk_k <= (qi + 1) * blk_q - 1)(update)
    else:
        update()

    @pl.when(kb == nk - 1)
    def _():
        l_safe = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / l_safe[:, :1]).astype(o_ref.dtype)
        m_fin = jnp.where(_kernel_finite(m_scr[:]), m_scr[:], 0.0)
        # scratch stays 128-lane; only the first _LSE_LANES lanes hit
        # HBM (every lane equal — lane 0 is the value)
        lse_ref[0] = (m_fin + jnp.log(l_safe))[:, :lse_ref.shape[-1]]


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
    # Under shard_map (ring/ulysses call this per shard), jax's vma
    # check requires pallas_call outputs to declare which mesh axes
    # they vary over — propagate the inputs' vma, even when EMPTY
    # (replicated q/k/v inside shard_map still need an explicit one).
    vma = frozenset().union(*(jax.typeof(t).vma for t in (q3, k3, v3)))
    o, lse_lanes = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, D), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_k, Dv), lambda b, i, j: (b, j, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, Dv), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, block_q, _LSE_LANES),
                         lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
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
    Pure XLA on purpose: it runs identically on TPU and in CPU tests,
    and XLA fuses the per-block einsums well.

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


def _flash3_bwd(scale, causal, block_q, block_k, use_pallas, res, g):
    return _bwd_chunked(res, g, scale=scale, causal=causal,
                        block_q=block_q)


_flash3.defvjp(_flash3_fwd, _flash3_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash3_lse(q3, k3, v3, scale, causal, block_q, block_k,
                use_pallas):
    """Like _flash3 but also returns the logsumexp [BH, T] — the
    statistic that makes attention outputs MERGEABLE (ring attention
    combines per-block results by lse weighting). Differentiable in
    both outputs (joint VJP in _bwd_chunked)."""
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
    return _bwd_chunked(res, g_o, g_lse, scale=scale, causal=causal,
                        block_q=block_q)


_flash3_lse.defvjp(_flash3_lse_fwd, _flash3_lse_bwd)


# Largest block_q*block_k score tile the kernel may hold in VMEM (f32;
# 512x512 = 1 MB — comfortable under the ~16 MB budget with q/k/v tiles
# and scratch). Only the degenerate-divisor path can exceed it.
_MAX_BLOCK_ELEMS = 512 * 512


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


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
    """Default block shape by sequence length: (512, 512) from
    T = 4096, (128, 128) below. Rests on a capture of 2026-07-31 on a
    v5e, before PR 1; record removed in PR 29; not measured on today's
    code (ROADMAP Speed 5).

    Both fit VMEM comfortably (<=1 MB score tile; _MAX_BLOCK_ELEMS).
    Note 'auto' attention dispatch routes T < 4096 to dense anyway
    (ops/attention_dispatch.py), so the sub-2048 default only governs
    explicit ``attention='flash'`` requests."""
    return (128, 128) if T <= 2048 else (512, 512)


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
    if force not in (None, "interpret", "xla"):
        raise ValueError(
            f"unknown force={force!r} (expected None, 'interpret', or "
            "'xla')")
    if force == "interpret":
        use_pallas = None           # pallas_call(interpret=True)
    elif force == "xla" or not on_tpu():
        use_pallas = False
    else:
        use_pallas = True
    if use_pallas and block_q * block_k > _MAX_BLOCK_ELEMS:
        # degenerate divisor (prime-ish T) collapsed to near-T blocks:
        # a [block_q, block_k] f32 score tile would blow VMEM on the
        # real lowering — the XLA oracle is the correct backend there
        use_pallas = False
    return (q3, k3, v3), (B, T, H, v.shape[-1]), scale, block_q, \
        block_k, use_pallas


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    force: Optional[str] = None) -> jnp.ndarray:
    """Exact attention, q and k [B, T, H, D], v and the result
    [B, T, H, Dv] (``Dv`` = ``D`` but for latent attention),
    differentiable.

    Backend selection: the Pallas kernel on TPU; its interpreter when
    ``force='interpret'`` (CPU kernel tests); the dense-oracle math
    otherwise (CPU training/eval — same semantics, standard memory).
    Block sizes default to the measured per-T winners
    (``_default_blocks``) and are adjusted to divisors of T (static
    shapes: decided once at trace time), so both the kernel grid and
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
