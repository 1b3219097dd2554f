"""Pallas TPU kernel: fused adaptive affine quantize->dequantize.

The XLA path (ops/quantize.py) lowers the compression transform as
separate min/max/mean reductions plus the elementwise round-trip — several
HBM passes over each payload tensor. This kernel fuses the whole transform
into ONE VMEM-resident pass: statistics and the round-trip happen while
the block is on-chip, which matters because the aggregation path is
HBM-bandwidth bound (one payload tensor per model parameter per round).

Semantics are identical to ops.quantize.quantize_dequantize (the
reference's flow_utils.py:169-212 affine scheme). Dispatch: the XLA
implementation off-TPU, for tensors past the tiled ceiling, when the
input is a vmap batch tracer (pallas_call has no batching rule), and
when the caller declares the client axis sharded over several devices
(no GSPMD partitioning rule) — so on a multi-chip mesh the UPLINK
quantizer is always XLA (algorithms/fedavg.py, fedgate.py pass
``sharded=mesh_devices > 1``); only a one-device mesh runs the kernel
there. chip_smoke.py proves which path compiled.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax._src.interpreters.batching import BatchTracer
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from fedtorch_tpu.ops.quantize import quantize_dequantize as _xla_qdq

_LANE = 128
# Per-tensor ceiling for the SINGLE-BLOCK kernel. The scoped-VMEM limit on
# real TPUs is 16 MB and the kernel's working set (input + output + mask /
# where temps) is ~5x the input, so the empirical ceiling on v5e is
# ~786k f32 elements (1M OOMs the compiler). 512k leaves headroom for the
# int16 path's wider temps. Larger tensors take the grid-tiled two-pass
# kernel below.
_MAX_VMEM_ELEMS = 512 * 1024
# Row-block height for the tiled kernel: (512, 128) f32 blocks = 256 KB.
_TILE_ROWS = 512
# Ceiling for the tiled path: beyond this just use XLA (tensors this large
# only appear in imagenet/transformer configs where the payload is sharded
# anyway, and the stats/apply sweeps stop paying for the extra launch).
_MAX_TILED_ELEMS = 64 * 1024 * 1024


def _affine_roundtrip(x, mn, mx, mean, num_bits: int):
    """The affine quantize->dequantize given precomputed stats — the ONE
    place the scheme (zero-scale epsilon, zp trunc/clip, round/clip)
    lives; shared by the single-block, batch, and tiled kernels so the
    paths cannot desynchronize."""
    qmin = -(2.0 ** (num_bits - 1))
    qmax = 2.0 ** (num_bits - 1) - 1.0
    scale = (mx - mn) / (qmax - qmin)
    scale = jnp.where(scale == 0.0, 0.001, scale)
    zp = jnp.trunc(jnp.clip(qmin - (mn - mean) / scale, qmin, qmax))
    q = jnp.clip(jnp.round(zp + (x - mean) / scale), qmin, qmax)
    return scale * (q - zp) + mean


def _qdq_math(x, n, num_bits: int):
    """The fused statistics + affine round-trip on one [rows, cols]
    VMEM-resident block with ``n`` valid leading elements."""
    rows, cols = x.shape
    flat_idx = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
                + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    valid = flat_idx < n

    big = jnp.asarray(jnp.finfo(jnp.float32).max)
    mn = jnp.min(jnp.where(valid, x, big))
    mx = jnp.max(jnp.where(valid, x, -big))
    mean = jnp.sum(jnp.where(valid, x, 0.0)) / n.astype(jnp.float32)
    return _affine_roundtrip(x, mn, mx, mean, num_bits)


def _qdq_kernel(n_ref, x_ref, out_ref, *, num_bits: int):
    out_ref[:] = _qdq_math(x_ref[:], n_ref[0], num_bits)


def _qdq_batch_kernel(n_ref, x_ref, out_ref, *, num_bits: int):
    """Grid-over-clients cell: one client's [1, rows, cols] block per
    program instance — statistics are PER CLIENT, exactly the vmapped
    per-client semantics of the uplink (fedavg.py:34-38)."""
    out_ref[0] = _qdq_math(x_ref[0], n_ref[0], num_bits)


def _tiled_stats_kernel(n_ref, x_ref, stats_ref):
    """Grid sweep 1: running [min, max, sum] over row-blocks.

    TPU grid steps run sequentially on the core, and ``stats_ref`` has a
    constant index map, so it stays resident and acts as an accumulator."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        stats_ref[0] = jnp.finfo(jnp.float32).max
        stats_ref[1] = -jnp.finfo(jnp.float32).max
        stats_ref[2] = 0.0

    x = x_ref[:]
    rows, cols = x.shape
    base = i * rows * cols
    flat_idx = base + (
        jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
        + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    valid = flat_idx < n_ref[0]
    big = jnp.asarray(jnp.finfo(jnp.float32).max)
    stats_ref[0] = jnp.minimum(stats_ref[0],
                               jnp.min(jnp.where(valid, x, big)))
    stats_ref[1] = jnp.maximum(stats_ref[1],
                               jnp.max(jnp.where(valid, x, -big)))
    stats_ref[2] = stats_ref[2] + jnp.sum(jnp.where(valid, x, 0.0))


def _tiled_apply_kernel(stats_ref, n_ref, x_ref, out_ref, *, num_bits: int):
    """Grid sweep 2: the affine round-trip with the global stats in SMEM."""
    mean = stats_ref[2] / n_ref[0].astype(jnp.float32)
    out_ref[:] = _affine_roundtrip(x_ref[:], stats_ref[0], stats_ref[1],
                                   mean, num_bits)


@functools.partial(jax.jit, static_argnames=("num_bits", "interpret"))
def _pallas_qdq_tiled(x2d: jnp.ndarray, n: jnp.ndarray,
                      num_bits: int,
                      interpret: bool = False) -> jnp.ndarray:
    """Two grid sweeps over (TILE_ROWS, LANE) blocks: stats, then apply.

    HBM traffic is 2 reads + 1 write of the payload — the same order as
    XLA's fused reduce+elementwise lowering, but with the stats guaranteed
    single-pass; exists so payloads past the single-block VMEM ceiling
    keep identical fused semantics instead of silently changing path."""
    rows = x2d.shape[0]
    nb = rows // _TILE_ROWS
    stats = pl.pallas_call(
        _tiled_stats_kernel,
        grid=(nb,),
        out_shape=jax.ShapeDtypeStruct((3,), jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_TILE_ROWS, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(n, x2d)
    return pl.pallas_call(
        functools.partial(_tiled_apply_kernel, num_bits=num_bits),
        grid=(nb,),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((_TILE_ROWS, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_TILE_ROWS, _LANE), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(stats, n, x2d)


@functools.partial(jax.jit, static_argnames=("num_bits", "interpret"))
def _pallas_qdq_padded(x2d: jnp.ndarray, n: jnp.ndarray,
                       num_bits: int,
                       interpret: bool = False) -> jnp.ndarray:
    return pl.pallas_call(
        functools.partial(_qdq_kernel, num_bits=num_bits),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        interpret=interpret,
    )(n, x2d)


@functools.partial(jax.jit, static_argnames=("num_bits", "interpret"))
def _pallas_qdq_batch_padded(x3d: jnp.ndarray, n: jnp.ndarray,
                             num_bits: int,
                             interpret: bool = False) -> jnp.ndarray:
    C, rows, lane = x3d.shape
    return pl.pallas_call(
        functools.partial(_qdq_batch_kernel, num_bits=num_bits),
        grid=(C,),
        out_shape=jax.ShapeDtypeStruct(x3d.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, rows, lane), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, rows, lane), lambda i: (i, 0, 0),
                               memory_space=pltpu.VMEM),
        interpret=interpret,
    )(n, x3d)


def fused_quantize_dequantize_batch(x: jnp.ndarray, num_bits: int = 8,
                                    force_pallas: bool = False,
                                    interpret: bool = False,
                                    sharded: bool = False) -> jnp.ndarray:
    """Per-slice quantize->dequantize over the LEADING axis: slice i gets
    its own statistics, identical to ``vmap(quantize_dequantize)``.

    This is the uplink kernel: the engine stacks the online clients'
    payloads as [k, ...] after the vmapped local loop, and the grid runs
    one program instance per client — covering the path the single-block
    kernel cannot (``pallas_call`` has no batching rule, so calling it
    under vmap falls back to XLA).

    ``sharded=True`` declares the leading axis sharded over multiple
    devices: the pallas custom call has no GSPMD partitioning rule, so
    the XLA path (which partitions cleanly) is used instead."""
    C = x.shape[0]
    n = 1
    for d in x.shape[1:]:
        n *= int(d)
    use_pallas = (force_pallas
                  or (_on_tpu() and n <= _MAX_VMEM_ELEMS
                      and not sharded)) \
        and not _is_batch_traced(x) and n > 0
    if not use_pallas:
        return jax.vmap(lambda v: _xla_qdq(v, num_bits))(x)
    rows = -(-n // _LANE)
    rows = -(-rows // 8) * 8
    padded = jnp.zeros((C, rows * _LANE), jnp.float32)
    padded = padded.at[:, :n].set(
        x.reshape(C, -1).astype(jnp.float32))
    out = _pallas_qdq_batch_padded(padded.reshape(C, rows, _LANE),
                                   jnp.asarray([n], jnp.int32), num_bits,
                                   interpret)
    return out.reshape(C, -1)[:, :n].reshape(x.shape).astype(x.dtype)


def fused_quantize_dequantize_tree(tree, num_bits: int = 8,
                                   leading_batch: bool = False,
                                   sharded: bool = False,
                                   force_pallas: bool = False,
                                   interpret: bool = False):
    """Per-tensor quantize->dequantize over a whole pytree, bucketed by
    flattened size: leaves of equal size are stacked and served by ONE
    client-grid kernel launch (per-slice stats keep exact per-tensor
    semantics).

    A resnet20 payload is ~117 leaves of only ~8 distinct sizes; the
    per-leaf path costs one kernel launch per leaf while bucketing costs
    one per distinct size, and per-tensor stats stay exact at every
    payload size. Its speed against per-leaf XLA on the chip is not
    measured (ROADMAP Speed 3).

    ``leading_batch=True`` marks uplink layout: each leaf carries a
    leading [k_online] axis and the bucket stacks to [b*k, n] so stats
    stay per (tensor, client). ``sharded=True`` (client axis split over
    devices) keeps the per-leaf XLA path — the pallas call has no GSPMD
    rule, and cross-device restacking would materialize transfers."""
    leaves, treedef = jax.tree.flatten(tree)
    if not leaves:
        return tree
    if (sharded or not (_on_tpu() or force_pallas)
            or any(_is_batch_traced(x) for x in leaves)):
        if leading_batch:
            out = [fused_quantize_dequantize_batch(x, num_bits,
                                                   sharded=sharded)
                   for x in leaves]
        else:
            out = [fused_quantize_dequantize(x, num_bits) for x in leaves]
        return jax.tree.unflatten(treedef, out)

    buckets = {}
    for i, x in enumerate(leaves):
        if leading_batch:
            # key on (leading dim, per-slice size): equal-sized leaves
            # with different batch dims must not share a reshape
            buckets.setdefault((x.shape[0], x.size // x.shape[0]),
                               []).append(i)
        else:
            buckets.setdefault((1, x.size), []).append(i)
    out = [None] * len(leaves)
    for (k, n), idxs in buckets.items():
        if n > _MAX_VMEM_ELEMS:
            # past the batch kernel's per-slice VMEM ceiling: the grid
            # kernel can't hold a slice, so serve each slice with the
            # per-leaf fused path (single-block or TILED kernel) instead
            # of letting the batch call silently fall back to XLA
            for i in idxs:
                leaf = leaves[i]
                if leading_batch:
                    qs = jnp.stack([
                        fused_quantize_dequantize(leaf[c], num_bits,
                                                  force_pallas, interpret)
                        for c in range(k)])
                    out[i] = qs.reshape(leaf.shape).astype(leaf.dtype)
                else:
                    out[i] = fused_quantize_dequantize(leaf, num_bits,
                                                       force_pallas,
                                                       interpret)
            continue
        if leading_batch:
            stacked = jnp.stack(
                [leaves[i].reshape(k, n) for i in idxs]).reshape(-1, n)
        else:
            stacked = jnp.stack([leaves[i].reshape(n) for i in idxs])
        q = fused_quantize_dequantize_batch(stacked, num_bits,
                                            force_pallas=force_pallas,
                                            interpret=interpret)
        if leading_batch:
            q = q.reshape(len(idxs), k, n)
        for j, i in enumerate(idxs):
            out[i] = q[j].reshape(leaves[i].shape).astype(leaves[i].dtype)
    return jax.tree.unflatten(treedef, out)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _is_batch_traced(x) -> bool:
    return isinstance(x, BatchTracer)


def fused_quantize_dequantize(x: jnp.ndarray, num_bits: int = 8,
                              force_pallas: bool = False,
                              interpret: bool = False) -> jnp.ndarray:
    """Drop-in replacement for ops.quantize.quantize_dequantize."""
    n = x.size
    use_pallas = (force_pallas
                  or (_on_tpu() and n <= _MAX_TILED_ELEMS)) \
        and not _is_batch_traced(x)
    if not use_pallas:
        return _xla_qdq(x, num_bits)
    if n <= _MAX_VMEM_ELEMS:
        rows = -(-n // _LANE)
        # pad rows to the f32 sublane multiple (8)
        rows = -(-rows // 8) * 8
        padded = jnp.zeros((rows * _LANE,), jnp.float32)
        padded = padded.at[:n].set(x.reshape(-1).astype(jnp.float32))
        out = _pallas_qdq_padded(padded.reshape(rows, _LANE),
                                 jnp.asarray([n], jnp.int32), num_bits,
                                 interpret)
    else:
        rows = -(-n // _LANE)
        rows = -(-rows // _TILE_ROWS) * _TILE_ROWS
        padded = jnp.zeros((rows * _LANE,), jnp.float32)
        padded = padded.at[:n].set(x.reshape(-1).astype(jnp.float32))
        out = _pallas_qdq_tiled(padded.reshape(rows, _LANE),
                                jnp.asarray([n], jnp.int32), num_bits,
                                interpret)
    return out.reshape(-1)[:n].reshape(x.shape).astype(x.dtype)
