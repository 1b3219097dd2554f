"""Device-side federated data layout.

The reference feeds each client from its own ``DataLoader``
(components/dataset.py:83-231); on TPU the whole federated dataset lives
on-device as ``[clients, N_max, ...]`` arrays padded per client with an
explicit size vector (SURVEY.md §7 'per-client heterogeneous dataset
sizes'), so batch selection happens *inside* the jitted round program —
no per-batch host->device copies (the reference pays an H2D copy per batch,
dataset.py:12-36).

Batch selection reproduces epoch semantics (each sample visited once per
epoch) via an in-graph random permutation per (client, epoch): uniform
keys with +inf on the padding tail, argsort, then wraparound indexing by
the local step counter.
"""
from __future__ import annotations

from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class ClientData(NamedTuple):
    """Per-client padded arrays. ``x: [C, N_max, ...]``, ``y: [C, N_max]``,
    ``sizes: [C]`` true sample counts."""
    x: jnp.ndarray
    y: jnp.ndarray
    sizes: jnp.ndarray

    @property
    def num_clients(self) -> int:
        return self.x.shape[0]

    @property
    def n_max(self) -> int:
        return self.x.shape[1]


def stack_partitions(features: np.ndarray, labels: np.ndarray,
                     partitions: Sequence[np.ndarray],
                     n_max: Optional[int] = None) -> ClientData:
    """Stack per-client index lists into padded device arrays.

    Padding repeats each client's own samples cyclically, so a padded row
    is always a *valid* sample of that client (masking is still applied
    for weighting, but a stray padded draw never injects another client's
    data)."""
    from fedtorch_tpu.native import cyclic_pad_indices, gather_rows
    sizes = np.asarray([len(p) for p in partitions])
    if np.any(sizes == 0):
        raise ValueError("Every client needs at least one sample; got a "
                         f"zero-sized partition (sizes={sizes.tolist()})")
    if n_max is None:
        n_max = int(sizes.max())
    # one flat padded index list -> one (native multithreaded) row gather
    idx_all = np.concatenate([
        cyclic_pad_indices(np.asarray(p, np.int32), n_max)
        for p in partitions])
    x = gather_rows(np.ascontiguousarray(features), idx_all)
    y = gather_rows(np.ascontiguousarray(labels), idx_all)
    C = len(partitions)
    # host (numpy) arrays: padding (pad_client_axis) and device placement
    # (shard_clients) both happen downstream — staying on host here means
    # device_put writes each shard straight to its device instead of
    # staging a full copy on device 0 first
    return ClientData(x=x.reshape((C, n_max) + x.shape[1:]),
                      y=y.reshape((C, n_max) + y.shape[1:]),
                      sizes=np.asarray(sizes, np.int32))


def pad_client_axis(data: ClientData, target_clients: int) -> ClientData:
    """Pad the leading client axis to ``target_clients`` with inert
    clients (zero rows, size 0) so it shards evenly over a device mesh.

    Padding clients are never selected by participation sampling (which
    draws from the real client range only) and carry ``sizes == 0`` so any
    size-masked statistic ignores them."""
    C = data.num_clients
    if target_clients == C:
        return data
    if target_clients < C:
        raise ValueError(
            f"target_clients={target_clients} < num_clients={C}")
    pad = target_clients - C

    def pad_leaf(a):
        # host-side when possible: np.concatenate avoids a transient
        # second full-dataset device allocation for device inputs
        xp = np if isinstance(a, np.ndarray) else jnp
        return xp.concatenate(
            [a, xp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0)

    return ClientData(x=pad_leaf(data.x), y=pad_leaf(data.y),
                      sizes=pad_leaf(data.sizes))


def epoch_permutation(rng: jax.Array, size: jnp.ndarray,
                      n_max: int) -> jnp.ndarray:
    """A random permutation of [0, size) padded (cyclically) to n_max.

    Uniform sort keys with +inf past ``size`` put all real samples first
    in random order; indexing past ``size`` wraps around."""
    keys = jax.random.uniform(rng, (n_max,))
    keys = jnp.where(jnp.arange(n_max) < size, keys, jnp.inf)
    return jnp.argsort(keys)


# Disjoint parent fold for a client's validation stream: the round
# program's dropout keys use folds [1, K] and augmentation 0x7FFFFFFF,
# so val lives at 0x7FFFFFFE (the train stream's fold 0 is already
# outside the dropout range).
VAL_FOLD = 0x7FFFFFFE


def round_row_plan(rng_c: jax.Array, size: jnp.ndarray, n_max: int,
                   num_rows: int, fold: int = 0) -> jnp.ndarray:
    """One client's row plan for a whole round: ``perm[(step*B + j) %
    size]`` for all ``num_rows = K*B`` (step, j) pairs — the
    :func:`epoch_permutation`/:func:`take_batch` batch order flattened
    (fold 0 = train stream, :data:`VAL_FOLD` = val stream).

    THE single definition of a round's batch order: the device round
    program ('batch' gather mode, parallel/federated.py) and the host
    streaming feed packer (data/streaming.py) both call it, so the two
    data planes cannot drift apart — which is what makes the
    ``data_plane='stream'`` bitwise-parity contract testable."""
    perm = epoch_permutation(jax.random.fold_in(rng_c, fold), size, n_max)
    return perm[jnp.arange(num_rows) % jnp.maximum(size, 1)]


def gather_client_rows(stores, idx: jnp.ndarray, rows: jnp.ndarray):
    """``store[idx[:, None], rows]`` for every ``[C, n_max, *sample]``
    store of the pytree ``stores`` (``idx: [k]`` clients, ``rows:
    [k, R]`` storage rows of each): the ``[k, R, *sample]`` rows, bit
    for bit, at a cost proportional to the COHORT's shards and never
    to ``C x n_max``.

    The one-gather spelling makes the whole store the gather's operand,
    and on the TPU that operand is what the compiler re-lays-out (the
    device keeps ``n_max`` minor-most, a row gather wants it major) and
    re-types (its bf16 propagation moves the model's input cast up
    through every data movement, optimization barriers included, to the
    program argument): a pass over all ``C x n_max`` samples each round.
    So the rows are taken one cohort member at a time, in one loop for
    all stores: take that client's shard off the leading axis, view it
    flat ``[n_max, features]``, gather its R rows. One shard is
    re-laid-out at a time, and floating stores move as raw bits
    (same-width unsigned integers), which no precision pass re-types:
    the cast lands on the ``[k, R]`` rows, where the model asked for it.

    The shard is taken with a one-index gather, not a
    ``dynamic_slice``: on one device they compile to the same slice,
    but where the client axis is split over devices the partitioner
    serves a gather from the shard's owner (one all-reduce of a shard)
    and answers a dynamic slice by all-gathering the store."""
    def client_rows(client):
        c, r = client

        def take(store):
            flat = store[c[None]].reshape((store.shape[1], -1))
            if not jnp.issubdtype(store.dtype, jnp.floating):
                return flat[r]
            bits = jnp.dtype(f"uint{8 * store.dtype.itemsize}")
            return jax.lax.bitcast_convert_type(
                jax.lax.bitcast_convert_type(flat, bits)[r], store.dtype)
        return jax.tree.map(take, stores)

    out = jax.lax.map(client_rows, (idx, rows))
    return jax.tree.map(
        lambda o, store: o.reshape(rows.shape + store.shape[2:]),
        out, stores)


def take_batch(data_x: jnp.ndarray, data_y: jnp.ndarray,
               perm: jnp.ndarray, size: jnp.ndarray,
               step_in_epoch: jnp.ndarray, batch_size: int):
    """Gather batch ``step_in_epoch`` from one client's permuted epoch.

    Index arithmetic wraps modulo the true client size, so short clients
    cycle within the epoch (the reference instead drops size-1 remainder
    batches, federated/main.py:104-106 — masking handles weighting here)."""
    offsets = step_in_epoch * batch_size + jnp.arange(batch_size)
    idx = perm[offsets % jnp.maximum(size, 1)]
    return data_x[idx], data_y[idx]


def sample_batch(rng: jax.Array, data_x: jnp.ndarray, data_y: jnp.ndarray,
                 size: jnp.ndarray, batch_size: int):
    """Uniform-with-replacement batch draw (used where the reference
    samples a single random batch, e.g. DRFA's loss phase)."""
    idx = jax.random.randint(rng, (batch_size,), 0,
                             jnp.maximum(size, 1))
    return data_x[idx], data_y[idx]


def train_val_split(partitions: Sequence[np.ndarray], val_fraction: float,
                    seed: int = 0):
    """Per-client train/val random split for personalization
    (components/dataset.py:168-211 random_split equivalent)."""
    rng = np.random.RandomState(seed)
    train_parts, val_parts = [], []
    for p in partitions:
        p = np.asarray(p)
        perm = rng.permutation(len(p))
        n_val = max(int(len(p) * val_fraction), 1) if len(p) > 1 else 0
        val_parts.append(p[perm[:n_val]])
        train_parts.append(p[perm[n_val:]])
    return train_parts, val_parts


def growing_batch_schedule(base_batch_size: int = 2,
                           max_batch_size: int = 0,
                           num_samples_per_epoch: int = 0,
                           num_epochs: Optional[int] = None,
                           num_iterations: Optional[int] = None,
                           rho: float = 1.01) -> List[int]:
    """Growing-minibatch schedule: the per-step batch sizes.

    Reference semantics (GrowingMinibatchSampler, components/
    dataset.py:276-317): ``batch_size[i] = int(base*rho^i) + 1`` with the
    iteration count derived from num_epochs (or vice versa) via the
    geometric-sum formula; sizes above ``max_batch_size`` are replaced by
    repeated max-size batches covering the same sample budget."""
    if num_epochs is None:
        if num_iterations is None:
            raise ValueError(
                "One of num_epochs or num_iterations must be provided.")
    else:
        num_iterations = int(
            np.log(num_samples_per_epoch * num_epochs * (rho - 1)
                   / base_batch_size + 1) / np.log(rho)) + 1
    batch_sizes = [int(base_batch_size * rho ** i) + 1
                   for i in range(num_iterations)]
    if max_batch_size:
        b = np.asarray(batch_sizes)
        over = np.flatnonzero(b > max_batch_size)
        if len(over) >= 1:
            overflow = int(np.sum(b[over]))
            batch_sizes = batch_sizes[:over[0]] \
                + [max_batch_size] * (overflow // max_batch_size)
            if overflow % max_batch_size:
                # the reference appends the remainder even when zero
                # (dataset.py:300-307) — an empty batch its loader skips;
                # we omit the no-op entry
                batch_sizes += [overflow % max_batch_size]
    return batch_sizes
