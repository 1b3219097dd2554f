"""Device mesh construction & sharding for the client axis.

Replaces the reference's process topology (``FCGraph``,
utils/topology.py:57-114: rank->block->device assignment over MPI
processes) with a ``jax.sharding.Mesh``: federated clients live on a
leading pytree axis that is sharded over the mesh's ``clients`` axis —
each device holds ``num_clients / num_devices`` clients and the aggregation
reduction becomes an XLA collective over ICI (SURVEY.md §2.10).

Multi-host (DCN) initialization mirrors ``dist.init_process_group``
(main.py:17) via ``jax.distributed.initialize``.
"""
from __future__ import annotations

import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from fedtorch_tpu.config import MeshConfig


def init_multihost(cfg: MeshConfig, *,
                   timeout_s: Optional[float] = None,
                   backoff_s: Optional[float] = None,
                   _sleep=time.sleep) -> None:
    """DCN bring-up for real pods (no-op for single-process runs).

    Pod bring-up is not atomic: workers boot at different speeds and the
    coordinator may accept connections seconds after the slowest worker
    first tries. A single-shot ``jax.distributed.initialize`` turns that
    skew into a whole-pod launch failure, so transient connect errors are
    retried with exponential backoff (``cfg.init_backoff_s`` doubling per
    attempt) until ``cfg.init_timeout_s`` is exhausted, then a clear
    timeout error names the coordinator instead of whatever socket-level
    exception the last attempt died with. Deterministic failures —
    malformed arguments (ValueError/TypeError) or double initialization
    — fail fast: retrying them would just burn the whole timeout on
    every host in the pod. ``_sleep`` is injectable for tests."""
    if cfg.coordinator_address is None:
        return
    # Multi-process CPU (the virtual-pod substrate every multihost test
    # runs on) needs an explicit cross-process collectives backend:
    # without one, the first sharded computation dies with
    # "Multiprocess computations aren't implemented on the CPU
    # backend". Gloo ships in jaxlib; set it only when the platform is
    # pinned to cpu (reading the config flag does NOT initialize a
    # backend — calling jax.default_backend() here would, breaking
    # distributed.initialize's must-run-first contract).
    platforms = (jax.config.jax_platforms or "").lower()
    if "cpu" in platforms.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    timeout_s = cfg.init_timeout_s if timeout_s is None else timeout_s
    backoff_s = cfg.init_backoff_s if backoff_s is None else backoff_s
    deadline = time.monotonic() + timeout_s
    attempt = 0
    while True:
        try:
            jax.distributed.initialize(
                coordinator_address=cfg.coordinator_address,
                num_processes=cfg.num_processes,
                process_id=cfg.process_id)
            return
        except (ValueError, TypeError):
            raise  # malformed address/ids — permanent, no retry
        except Exception as e:
            msg = str(e).lower()
            # double jax.distributed.initialize — permanent ("distributed
            # .initialize should only be called once." in current JAX;
            # older/newer wordings say "already initialized")
            if "only be called once" in msg or (
                    "already" in msg and "initial" in msg):
                raise
            attempt += 1
            delay = backoff_s * (2.0 ** (attempt - 1))
            if time.monotonic() + delay > deadline:
                raise RuntimeError(
                    f"init_multihost: could not reach coordinator "
                    f"{cfg.coordinator_address!r} within {timeout_s:.0f}s "
                    f"({attempt} attempt(s); process_id="
                    f"{cfg.process_id}, num_processes="
                    f"{cfg.num_processes}). Check that the coordinator "
                    "process is up and the address/port is reachable "
                    f"from this host. Last error: {e!r}") from e
            _sleep(delay)


def make_mesh(cfg: MeshConfig, num_clients: Optional[int] = None) -> Mesh:
    """1-D mesh over all (or the first ``num_devices``) devices — or,
    with ``cfg.client_shards > 1``, the pod-scale 2-D
    ``[client_shards, devices/client_shards]`` mesh whose leading axis
    shards the round's ONLINE COHORT (docs/performance.md "Pod-scale
    round programs").

    Every requested device is always used: when ``num_clients`` does not
    divide the device count, the engine pads the client axis with inert
    zero-weight clients (:func:`padded_client_count`) instead of idling
    chips — SURVEY.md §7's ``[cores, clients_per_core]`` layout. The
    ``num_clients`` argument is kept for API compatibility; it no longer
    constrains the mesh.

    The 2-D reshape is row-major, so the FLAT device order — and with
    it the resident ``[C]`` client-state placement under
    :func:`client_sharding` — is byte-identical for every shard count
    on the same devices: only the cohort axis re-shards, which is what
    makes S-shard-vs-1-shard rounds (and degraded-pod resume onto
    fewer shards) bitwise."""
    del num_clients  # padding, not divisor-clamping, handles remainders
    devices = jax.devices(cfg.backend) if cfg.backend else jax.devices()
    n = cfg.num_devices or len(devices)
    n = min(n, len(devices))
    shards = max(int(getattr(cfg, "client_shards", 0) or 0), 0)
    if shards >= 1:
        # client_shards == 1 still builds the 2-D [1, n] mesh: the
        # armed 1-shard twin must carry the exact cohort-sharding
        # structure of its S-shard siblings (cohort axis over a
        # leading mesh axis of size S) for the bitwise-parity bar
        if n % shards:
            raise ValueError(
                f"mesh.client_shards={shards} does not divide the "
                f"{n}-device mesh — the cohort shards are contiguous "
                "device groups, so the device count must be a "
                "multiple of the shard count")
        return Mesh(np.asarray(devices[:n]).reshape(shards, n // shards),
                    (cfg.axis_name, cfg.axis_name + "_rep"))
    return Mesh(np.asarray(devices[:n]), (cfg.axis_name,))


def padded_client_count(num_clients: int, mesh: Mesh) -> int:
    """Smallest multiple of the mesh size >= ``num_clients``.

    The gap is filled with padding clients that are never sampled by
    ``participation_indices`` (which permutes only the REAL client range),
    so they contribute zero FLOPs to training and zero weight to
    aggregation — they exist purely so the client axis shards evenly over
    all devices."""
    n = int(mesh.devices.size)
    return -(-num_clients // n) * n


def client_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading [C] client axis over ALL mesh axes — on the
    pod-scale 2-D mesh the row-major flattening reproduces the 1-D
    device order exactly, so resident client state occupies the same
    device blocks at every ``client_shards`` setting. A legacy 1-D
    mesh keeps the single-name spec (not a 1-tuple): the spec objects
    are semantically equal but not ``==``, and a changed spec on the
    disarmed path perturbs the jit executable-cache keys the
    trace-once tests pin."""
    if len(mesh.axis_names) == 1:
        return NamedSharding(mesh, P(mesh.axis_names[0]))
    return NamedSharding(mesh, P(tuple(mesh.axis_names)))


def cohort_sharding(mesh: Mesh) -> NamedSharding:
    """Shard a leading [k] ONLINE-COHORT axis over the client-shard
    axis only (replicated across the per-shard device group): each of
    the S contiguous shard groups executes its k/S clients and the
    aggregation seam's one all-reduce recombines the partials
    (docs/performance.md "Pod-scale round programs"). On a 1-D mesh
    this degenerates to :func:`client_sharding`."""
    return NamedSharding(mesh, P(mesh.axis_names[0]))


def mesh_client_shards(mesh: Mesh) -> int:
    """Shard count of the cohort axis: the leading dim of the 2-D
    pod-scale mesh, 1 on a legacy 1-D mesh."""
    return int(mesh.devices.shape[0]) if mesh.devices.ndim > 1 else 1


def local_cohort_rows(mesh: Mesh, k: int, shards: int):
    """``[lo, hi)`` cohort rows owned by THIS process's devices under
    S-way client sharding — the slice its feed producer must pack
    (per-host H2D bytes and host RAM cut by the shard count). Shards
    are contiguous row blocks of k/S; a process owning shard rows
    [s0, s1) owns cohort rows [s0*k/S, s1*k/S). Falls back to the full
    range for unsharded runs or a non-contiguous device-to-process
    layout (correct, just not minimal)."""
    if shards <= 1 or k % shards or mesh.devices.ndim < 2:
        return 0, k
    per = k // shards
    pid = jax.process_index()
    mine = [s for s in range(shards)
            if any(d.process_index == pid
                   for d in np.asarray(mesh.devices)[s].flat)]
    if not mine:
        return 0, k
    lo, hi = min(mine), max(mine) + 1
    if mine != list(range(lo, hi)):
        return 0, k
    return lo * per, hi * per


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def _put(x, sh: NamedSharding):
    """Multihost-aware placement: ``device_put`` only accepts fully
    addressable shardings, so on a multi-process (DCN) mesh the global
    array is assembled from each process's slice of the host data. Every
    process holds identical host data (the shared-seed determinism
    contract, docs/multihost.md), so the local slice is just a view."""
    if sh.is_fully_addressable:
        return jax.device_put(x, sh)
    dt = getattr(x, "dtype", None)
    if dt is not None and jnp.issubdtype(dt, jax.dtypes.prng_key):
        # typed PRNG keys can't round-trip through numpy; carry the raw
        # key data (the spec applies to leading axes, so the trailing
        # key-word dimension is unaffected)
        data = _put(jax.random.key_data(x), sh)
        return jax.random.wrap_key_data(data, impl=jax.random.key_impl(x))
    return jax.make_array_from_process_local_data(sh, np.asarray(x))


def shard_clients(tree, mesh: Mesh):
    """Place a [C, ...] pytree with the client axis split over devices."""
    sh = client_sharding(mesh)
    return jax.tree.map(lambda x: _put(x, sh), tree)


def replicate(tree, mesh: Mesh):
    sh = replicated_sharding(mesh)
    return jax.tree.map(lambda x: _put(x, sh), tree)
