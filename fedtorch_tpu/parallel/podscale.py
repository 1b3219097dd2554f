"""Pod-scale hierarchical aggregation: the client-axis sharded twin of
``_round_core``'s weighted payload sum (docs/performance.md "Pod-scale
round programs").

The k online clients of a round are sharded over S contiguous device
groups (``mesh.py:cohort_sharding``); each shard executes its k/S
clients' local loops and holds its slice of the stacked ``[k, ...]``
payloads. The aggregation seam must then reduce across shards — and the
reduction is the ONE place client sharding could break the engine-wide
bitwise bar, because float addition is not associative: a plain
``jnp.sum`` (or ``psum``) lets XLA pick a different add order per shard
count.

:func:`cohort_hierarchical_sum` instead fixes the association as a
function of k ALONE, so every shard count S (including the unsharded
S=1 twin) performs the identical scalar add sequence:

* the k clients are split into ``G = min(64, largest power of two
  dividing k)`` groups of k/G consecutive clients;
* **level 1** — each group's partial is an explicit left-deep chain
  over its members (``acc = x[0]; acc += x[1]; ...``), computed on the
  shard that owns the group (S | G by the cell validator's power-of-two
  rules, so groups never straddle shards);
* **collective** — the G group partials are combined with exactly ONE
  ``jax.lax.all_gather`` over the client-shard axis (the explicit
  collective FTP004 certifies; shard order == global group order
  because cohort shards are contiguous blocks);
* **level 2** — one left-deep chain over the G gathered partials,
  identical on every device.

Both chains' lengths and orders depend only on k, never on S —
S-shard-vs-1-shard parity is bitwise by construction, and a degraded
pod resuming an S-shard checkpoint onto S/2 shards replays the same
sums. Integer payload leaves (quantized wire formats) take a plain
``jnp.sum``: integer addition is exact under any association, and
keeping them out of the gather holds the explicit-collective count at
one.

The collective is an all-gather rather than a literal ``psum`` so the
level-2 adds stay explicit (a psum would hand the partial-combine
order back to the compiler); semantically it IS the round's one
cross-shard all-reduce — gather + identical local reduction on every
shard.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

# cap on the deterministic group count: bounds the unrolled level-2
# add chain (and with it program size) while leaving every shard count
# up to a 64-host pod a whole number of groups per shard
MAX_AGG_GROUPS = 64


def cohort_group_count(k: int) -> int:
    """G — the S-invariant group count for a k-wide cohort: the
    largest power of two dividing k, capped at :data:`MAX_AGG_GROUPS`.
    A function of k ONLY (never of the shard count), which is the
    whole bitwise-parity argument."""
    if k <= 0:
        raise ValueError(f"cohort width must be positive, got {k}")
    return min(MAX_AGG_GROUPS, k & -k)


def _left_deep(rows):
    """Explicit left-deep add chain over a leading axis — the one
    association every shard count replays."""
    acc = rows[0]
    for i in range(1, rows.shape[0]):
        acc = acc + rows[i]
    return acc


def _group_partials(flat: jnp.ndarray, groups: int) -> jnp.ndarray:
    """[rows, P] -> [groups, P] level-1 partials: left-deep over each
    group's rows/groups consecutive members."""
    per = flat.shape[0] // groups
    xg = flat.reshape(groups, per, flat.shape[1])
    acc = xg[:, 0]
    for j in range(1, per):
        acc = acc + xg[:, j]
    return acc


def cohort_allreduce_bytes(payloads, k: int) -> float:
    """Bytes the seam's one all-gather moves onto each device per
    round: the full [G, P] float partial stack. Static (aval-only);
    feeds the ``cohort_allreduce_bytes`` telemetry gauge."""
    total = 0
    for leaf in jax.tree.leaves(payloads):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            n = int(np.prod(leaf.shape[1:])) if leaf.ndim > 1 else 1
            total += n * jnp.dtype(leaf.dtype).itemsize
    return float(cohort_group_count(k) * total)


def cohort_hierarchical_sum(payloads, mesh: Mesh, shards: int):
    """Sum the stacked ``[k, ...]`` payload pytree over the cohort
    axis with the S-invariant grouped association (module docstring).
    ``shards <= 1`` runs the identical chains without the collective —
    the bitwise twin every sharded cell is pinned against."""
    leaves, treedef = jax.tree.flatten(payloads)
    out = [None] * len(leaves)
    float_ix = []
    for i, leaf in enumerate(leaves):
        if jnp.issubdtype(leaf.dtype, jnp.floating):
            float_ix.append(i)
        else:
            # integer wire leaves: exact under any association, and
            # excluded from the gather so the explicit-collective
            # count stays at exactly one
            out[i] = jnp.sum(leaf, axis=0)
    if not float_ix:
        return jax.tree.unflatten(treedef, out)

    k = leaves[float_ix[0]].shape[0]
    groups = cohort_group_count(k)
    if shards > 1:
        if k % shards or groups % shards:
            raise ValueError(
                f"cohort width {k} does not shard {shards} ways "
                "(validate_cell refuses this cell)")
    shapes = [leaves[i].shape[1:] for i in float_ix]
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    flat = jnp.concatenate(
        [leaves[i].reshape(k, -1) for i in float_ix], axis=1)

    if shards > 1:
        axis = mesh.axis_names[0]

        def per_shard(block):
            # block: this shard's [k/S, P] slice = G/S whole groups
            partial = _group_partials(block, groups // shards)
            full = jax.lax.all_gather(partial, axis, axis=0,
                                      tiled=True)  # [G, P], global order
            return _left_deep(full)

        summed = jax.shard_map(
            per_shard, mesh=mesh, in_specs=P(axis), out_specs=P(),
            check_vma=False)(flat)
    else:
        summed = _left_deep(_group_partials(flat, groups))

    off = 0
    for i, size, shape in zip(float_ix, sizes, shapes):
        out[i] = summed[off:off + size].reshape(shape)
        off += size
    return jax.tree.unflatten(treedef, out)
