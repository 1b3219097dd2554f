"""Streaming data plane: host-resident client store + round-ahead feeds.

The device data plane (the seed behavior) shards the ENTIRE federation
dataset into HBM at trainer construction and hands the full
``[C, n_max, ...]`` pytree to every jitted round — population size is
capped by device memory even though a round only ever touches the K
online clients' ``K*B`` rows. ``cfg.data.data_plane='stream'`` keeps
the client store host-resident and turns each round's working set into
a packed :class:`RoundFeed`:

* **Schedule replay.** Participation and per-client batch order derive
  deterministically from the threefry key schedule
  (``fold_in(server.rng, round)`` → ``participation_indices`` →
  ``round_row_plan``). :class:`RoundSchedule` runs the SAME jax PRNG
  ops on the CPU backend, so the host knows round r+1's exact index
  plan without touching the accelerator stream.
* **Packed gather.** The K online clients' rows are gathered from the
  host store with the native multithreaded ``ft_gather_rows`` (numpy
  fallback — bitwise-identical output either way, pinned by
  tests/test_streaming.py) into ``[k, K*B, ...]`` feed tensors.
* **Round-ahead overlap.** A background producer
  (:class:`~fedtorch_tpu.native.host_pipeline.HostPrefetcher`) builds
  and ``jax.device_put``\\ s round r+1's feed WHILE round r computes —
  double-buffered, so the steady-state H2D transfer hides under device
  compute and device-side data residency drops from ``O(C*n_max)`` to
  ``O(2*k*K*B)``.
* **Feed windows** (the scanned streamed program —
  parallel/round_program.py): under the scan dispatch the producer
  packs ``window`` consecutive rounds into one ``[R, k, K*B, ...]``
  stacked feed (ONE flat gather per tensor — ``pack_window``) and the
  device ``lax.scan``\\ s window r while window r+1 builds; residency
  becomes ``O((depth+1)*R*k*K*B)`` — R trades device memory for
  dispatch count.
* **The million-client store** (docs/performance.md): the store behind
  the gathers is a :class:`ClientStore` seam with two implementations —
  :class:`HostClientStore` (the in-RAM ``[C, n_max, ...]`` arrays, the
  seed behavior) and :class:`MmapClientStore` (``np.memmap`` views over
  a manifest-described sharded file layout, so the population lives on
  DISK and host residency is O(feed), not O(C)). ``pack`` is one flat
  row gather per tensor either way: the native ``ft_gather_rows``
  reads flat buffers, so mmap is a file-descriptor swap.

The trainer-side consumer is ``FederatedTrainer.round_stream_fn``
(parallel/federated.py) — per feed, or scanned over the window —
which funnels into the same ``_round_core`` the device plane uses:
the bitwise-parity contract holds in every cell.
"""
from __future__ import annotations

import contextlib
import json
import os
import pathlib
import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from fedtorch_tpu import telemetry
from fedtorch_tpu.data.batching import ClientData, round_row_plan
from fedtorch_tpu.native.host_pipeline import HostPrefetcher, gather_rows
from fedtorch_tpu.robustness import host_chaos, host_recovery

#: manifest schema of the on-disk sharded client store (MmapClientStore)
STORE_FORMAT = "fedtorch-client-store"
STORE_VERSION = 1
MANIFEST_NAME = "manifest.json"
SIZES_NAME = "sizes.int32.bin"


class RoundFeed(NamedTuple):
    """One round's device inputs under the streaming plane.

    ``x``/``y`` hold the round's pre-selected rows in
    ``round_row_plan`` order (the 'batch' gather layout) or each
    client's WHOLE padded shard in storage order (the 'shard' feed
    layout — full-loss algorithms like qFFL scan every row);
    ``pre_x``/``pre_y`` are each online client's first B storage-order
    rows — what the ``pre_round`` hook sees in every gather mode.
    ``probe_*`` are the optional post-round probe batches (DRFA's dual
    phase — ``FedAlgorithm.host_probe_fn``); None leaves vanish from
    the pytree, so feeds without a probe trace the pre-probe program."""
    idx: jnp.ndarray      # [k] int32 online-client ids
    sizes: jnp.ndarray    # [k] int32 true sample counts
    x: jnp.ndarray        # [k, K*B, ...] (batch) or [k, n_max, ...] (shard)
    y: jnp.ndarray        # [k, K*B, ...] / [k, n_max, ...]
    pre_x: jnp.ndarray    # [k, B, ...]
    pre_y: jnp.ndarray    # [k, B, ...]
    probe_idx: Optional[jnp.ndarray] = None  # [k2] int32 probe-client ids
    probe_x: Optional[jnp.ndarray] = None    # [k2, B, ...]
    probe_y: Optional[jnp.ndarray] = None    # [k2, B, ...]


def feed_nbytes(feed: RoundFeed) -> int:
    """Byte count of one packed feed (the unit of the streaming
    plane's device residency: steady state holds at most the prefetch
    depth of these, not the client store). Delegates to the one byte
    accounting helper (``core.state.tree_bytes`` — also the
    comm_bytes unit), so the two cannot drift."""
    from fedtorch_tpu.core.state import tree_bytes
    return int(tree_bytes(feed))


def _as_host_array(a, dtype=None) -> np.ndarray:
    """Host view of ``a``, contiguous, ZERO-COPY when the input is
    already a contiguous host array of the right dtype (the store
    constructor's no-silent-duplication contract — at million-client
    scale an accidental copy doubles peak host RAM). Only a
    non-contiguous or wrong-dtype input pays a materialization."""
    a = np.asarray(a) if dtype is None else np.asarray(a, dtype=dtype)
    return a if a.flags.c_contiguous else np.ascontiguousarray(a)


class ClientStore:
    """The host client-store seam: everything the feed producer needs
    from a population, behind ONE flat-row gather hook.

    Subclasses provide storage (:class:`HostClientStore` keeps the
    ``[C, n_max, ...]`` arrays in RAM; :class:`MmapClientStore` maps a
    manifest-described shard layout straight off disk) and implement
    :meth:`_gather_flat`; the packing arithmetic — flat row ids, the
    clamped ``pre_round`` columns, the window flatten — is shared here,
    so the two stores cannot drift and ``RoundFeed`` bytes are
    identical for the same schedule (tests/test_streaming.py)."""

    # subclasses populate these in __init__
    num_clients: int
    n_max: int
    sizes: np.ndarray            # [C] int32, always RAM-resident
    _feat: dict                  # tensor name -> trailing feature shape
    _dtypes: dict                # tensor name -> np.dtype

    def _gather_flat(self, tensor: str,
                     flat_rows: np.ndarray) -> np.ndarray:
        """``out[i] = store[tensor].reshape(C*n_max, ...)[flat_rows[i]]``
        — contiguous output, bitwise-identical across implementations."""
        raise NotImplementedError

    def feat(self, tensor: str) -> tuple:
        """Trailing per-sample feature shape of ``tensor``."""
        return tuple(self._feat[tensor])

    def dtype(self, tensor: str) -> np.dtype:
        return self._dtypes[tensor]

    # -- residency accounting (the population-scaling evidence) --------
    @property
    def resident_nbytes(self) -> int:
        """Bytes this store pins in host RAM."""
        raise NotImplementedError

    @property
    def mapped_nbytes(self) -> int:
        """Bytes addressable through mmap (paged on demand, evictable
        — NOT resident)."""
        raise NotImplementedError

    @property
    def nbytes(self) -> int:
        return int(self.resident_nbytes + self.mapped_nbytes)

    # -- packing -------------------------------------------------------
    def pack(self, idx: np.ndarray, rows: np.ndarray,
             batch_size: int) -> RoundFeed:
        """Pack one round's feed: client ``idx[i]``'s rows ``rows[i]``
        plus its first ``batch_size`` storage-order rows (the
        ``pre_round`` hook batch). Output is bitwise-identical whether
        the native library or the numpy fallback does the gather."""
        idx = np.asarray(idx, np.int64)
        rows = np.asarray(rows, np.int64)
        k, num_rows = rows.shape
        flat = (idx[:, None] * self.n_max + rows).reshape(-1)
        # clamp like the device plane's jnp gather does: with
        # batch_size > n_max the hook batch repeats the last row
        # instead of walking into the next client's shard
        pre_cols = np.minimum(np.arange(batch_size, dtype=np.int64),
                              self.n_max - 1)
        pre = (idx[:, None] * self.n_max + pre_cols[None, :]).reshape(-1)
        feat_x, feat_y = self._feat["x"], self._feat["y"]
        return RoundFeed(
            idx=idx.astype(np.int32),
            sizes=self.sizes[idx],
            x=self._gather_flat("x", flat).reshape(
                (k, num_rows) + feat_x),
            y=self._gather_flat("y", flat).reshape(
                (k, num_rows) + feat_y),
            pre_x=self._gather_flat("x", pre).reshape(
                (k, batch_size) + feat_x),
            pre_y=self._gather_flat("y", pre).reshape(
                (k, batch_size) + feat_y))

    def pack_shards(self, idx: np.ndarray, batch_size: int) -> RoundFeed:
        """The 'shard' feed layout: each online client's WHOLE padded
        shard in storage order — what full-loss algorithms (qFFL)
        consume on the stream plane. Row selection then happens
        in-program (``epoch_permutation`` inside ``client_round``),
        exactly like the device plane's shard gather mode."""
        idx = np.asarray(idx, np.int64)
        rows = np.broadcast_to(np.arange(self.n_max, dtype=np.int64),
                               (idx.shape[0], self.n_max))
        return self.pack(idx, rows, batch_size)

    def pack_probe(self, idx2: np.ndarray, rows2: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Gather the post-round probe batches (DRFA's dual phase):
        client ``idx2[i]``'s storage rows ``rows2[i]`` (already
        size-clamped by the host probe replay). One flat gather per
        tensor, same as :meth:`pack`."""
        idx2 = np.asarray(idx2, np.int64)
        rows2 = np.asarray(rows2, np.int64)
        k2, b = rows2.shape
        flat = (idx2[:, None] * self.n_max + rows2).reshape(-1)
        return (idx2.astype(np.int32),
                self._gather_flat("x", flat).reshape(
                    (k2, b) + self._feat["x"]),
                self._gather_flat("y", flat).reshape(
                    (k2, b) + self._feat["y"]))

    def pack_window(self, idxs: np.ndarray, rowss: np.ndarray,
                    batch_size: int) -> RoundFeed:
        """Pack an ``[R, ...]``-stacked feed WINDOW (the scanned
        streamed program's input) in ONE gather per tensor: the R
        rounds' ``[R, k]`` client ids and ``[R, k, rows]`` row plans
        flatten to an ``[R*k]``-client pack, and the contiguous
        reshape back to ``[R, k, ...]`` is free — no per-round
        feeds + stack copy."""
        R, k = np.asarray(idxs).shape
        feed = self.pack(np.asarray(idxs).reshape(-1),
                         np.asarray(rowss).reshape(R * k, -1),
                         batch_size)
        return RoundFeed(*(a.reshape((R, k) + a.shape[1:])
                           if a is not None else None for a in feed))


class HostClientStore(ClientStore):
    """The in-RAM client store: ``[C, n_max, ...]`` numpy arrays plus
    flat row views, so one round's feed is ONE (native, multithreaded)
    row gather per tensor instead of per-client copies.

    This is the piece that unbinds population size from HBM: the store
    can be as large as host RAM. The arrays are NEVER copied here when
    the input is already contiguous host memory (``np.shares_memory``
    pinned by tests/test_streaming.py) — past host RAM, swap the seam
    for :class:`MmapClientStore` and the population lives on disk."""

    def __init__(self, data: ClientData):
        self.x = _as_host_array(data.x)
        self.y = _as_host_array(data.y)
        self.sizes = _as_host_array(data.sizes, dtype=np.int32)
        self.num_clients, self.n_max = self.x.shape[:2]
        self._feat = {"x": self.x.shape[2:], "y": self.y.shape[2:]}
        self._dtypes = {"x": self.x.dtype, "y": self.y.dtype}
        self._flat = {
            "x": self.x.reshape((self.num_clients * self.n_max,)
                                + self.x.shape[2:]),
            "y": self.y.reshape((self.num_clients * self.n_max,)
                                + self.y.shape[2:]),
        }
        # ft_gather_rows indexes with int32; a store past 2^31-1 total
        # rows falls back to numpy fancy indexing
        self._native_ok = (self.num_clients * self.n_max
                           <= np.iinfo(np.int32).max)

    @property
    def resident_nbytes(self) -> int:
        return int(self.x.nbytes + self.y.nbytes)

    @property
    def mapped_nbytes(self) -> int:
        return 0

    def _gather_flat(self, tensor: str,
                     flat_rows: np.ndarray) -> np.ndarray:
        src = self._flat[tensor]
        if self._native_ok:
            return gather_rows(src, flat_rows.astype(np.int32))
        return np.ascontiguousarray(src[flat_rows])


class MmapClientStore(ClientStore):
    """The disk-backed client store: ``np.memmap`` views over a
    manifest-described shard layout (:func:`save_client_store` /
    :class:`MmapStoreWriter` materialize one), so host RESIDENCY is
    O(feed) while the population is bounded by disk.

    Layout (``manifest.json``): clients are split into consecutive
    shards of ``clients_per_shard``; each shard is one raw C-order
    file of ``[clients_in_shard * n_max, ...feat]`` rows per tensor.
    A gather touches only the shards its rows land in, maps them
    lazily, and indexes each with LOCAL int32 row ids — so the native
    ``ft_gather_rows`` path stays correct past 2^31 total rows (the
    per-shard row count is capped at int32 by construction; the
    in-RAM store must fall back to numpy there). ``sizes`` loads to
    RAM (4 bytes/client — the one O(C) host cost).

    A torn/truncated shard file surfaces at gather time (the mmap
    length check), which the feed producer's 'stream.gather' bounded
    retry turns into a named ``HostSeamError`` — the read-hiccup path
    :meth:`StreamFeedProducer._pack_feed` anticipates."""

    def __init__(self, store_dir: str):
        self._dir = pathlib.Path(store_dir)
        mpath = self._dir / MANIFEST_NAME
        if not mpath.is_file():
            raise ValueError(
                f"no client-store manifest at {mpath} — materialize "
                "one with fedtorch_tpu.data.streaming.save_client_store "
                "(or MmapStoreWriter) and point data.store_dir at it")
        with open(mpath, "r", encoding="utf-8") as f:
            man = json.load(f)
        if man.get("format") != STORE_FORMAT:
            raise ValueError(
                f"{mpath}: format {man.get('format')!r} is not "
                f"{STORE_FORMAT!r}")
        if int(man.get("version", -1)) != STORE_VERSION:
            raise ValueError(
                f"{mpath}: version {man.get('version')!r} unsupported "
                f"(this build reads version {STORE_VERSION})")
        self.num_clients = int(man["num_clients"])
        self.n_max = int(man["n_max"])
        self.clients_per_shard = int(man["clients_per_shard"])
        if self.clients_per_shard * self.n_max > np.iinfo(np.int32).max:
            raise ValueError(
                f"{mpath}: clients_per_shard * n_max "
                f"({self.clients_per_shard} * {self.n_max}) overflows "
                "int32 — the per-shard native gather contract")
        num_shards = -(-self.num_clients // self.clients_per_shard)
        self.sizes = np.fromfile(str(self._dir / man["sizes_file"]),
                                 dtype=np.int32)
        if self.sizes.shape[0] != self.num_clients:
            raise ValueError(
                f"{self._dir / man['sizes_file']}: {self.sizes.shape[0]} "
                f"sizes for {self.num_clients} clients")
        self._feat, self._dtypes, self._paths = {}, {}, {}
        for name, spec in man["tensors"].items():
            self._feat[name] = tuple(int(d) for d in spec["feat"])
            self._dtypes[name] = np.dtype(spec["dtype"])
            paths = [self._dir / p for p in spec["shards"]]
            if len(paths) != num_shards:
                raise ValueError(
                    f"{mpath}: tensor {name!r} lists {len(paths)} "
                    f"shards, layout needs {num_shards}")
            self._paths[name] = paths
        self._maps: dict = {}  # (tensor, shard id) -> np.memmap

    @property
    def resident_nbytes(self) -> int:
        return int(self.sizes.nbytes)

    @property
    def mapped_nbytes(self) -> int:
        total = 0
        for name in self._paths:
            row = self._dtypes[name].itemsize * int(
                np.prod(self._feat[name], initial=1))
            total += self.num_clients * self.n_max * row
        return int(total)

    def _shard_clients(self, sid: int) -> int:
        lo = sid * self.clients_per_shard
        return min(self.clients_per_shard, self.num_clients - lo)

    def _shard(self, tensor: str, sid: int) -> np.memmap:
        key = (tensor, sid)
        mm = self._maps.get(key)
        if mm is None:
            shape = ((self._shard_clients(sid) * self.n_max,)
                     + self._feat[tensor])
            try:
                # raises if the file is torn/truncated (mmap length
                # check) — the producer's 'stream.gather' retry seam
                # owns that, escalating through 'stream.producer'
                mm = np.memmap(str(self._paths[tensor][sid]),
                               dtype=self._dtypes[tensor], mode="r",
                               shape=shape)
            except (ValueError, OSError) as e:
                # name the owner: under pod-scale per-host sharded
                # packing (docs/multihost.md) the recovery chain must
                # say WHICH host's store shard tore, not just that a
                # gather failed somewhere in the pod
                raise ValueError(
                    f"client-store shard {sid} of tensor {tensor!r} "
                    f"(owning host: process {jax.process_index()}) is "
                    "torn or truncated at "
                    f"{self._paths[tensor][sid]} — expected "
                    f"{int(np.prod(shape))} x "
                    f"{self._dtypes[tensor]} elements; {e}") from e
            self._maps[key] = mm
        return mm

    def _gather_flat(self, tensor: str,
                     flat_rows: np.ndarray) -> np.ndarray:
        rows_per_shard = self.clients_per_shard * self.n_max
        sid = flat_rows // rows_per_shard
        out = np.empty((flat_rows.shape[0],) + self._feat[tensor],
                       self._dtypes[tensor])
        for s in np.unique(sid):
            m = sid == s
            local = flat_rows[m] - int(s) * rows_per_shard
            out[m] = gather_rows(self._shard(tensor, int(s)),
                                 local.astype(np.int32))
        return out

    def as_client_data(self) -> ClientData:
        """A zero-RAM ``ClientData`` VIEW for trainer construction:
        ``sizes`` is the real array; ``x``/``y`` are stride-0
        broadcast stubs with the true shape/dtype (algorithm ``setup``
        and the trainer's shape probes read metadata only — on the
        stream plane the arrays themselves are never uploaded)."""
        C, n = self.num_clients, self.n_max
        x = np.broadcast_to(np.zeros((), self._dtypes["x"]),
                            (C, n) + self._feat["x"])
        y = np.broadcast_to(np.zeros((), self._dtypes["y"]),
                            (C, n) + self._feat["y"])
        return ClientData(x=x, y=y, sizes=self.sizes)


class MmapStoreWriter:
    """Incremental builder for the on-disk sharded client store:
    append ``[c, n_max, ...]`` client chunks (so a 10^6-client
    synthetic population materializes chunk-wise without ever holding
    ``[C, n_max, ...]`` in RAM), then :meth:`finalize` writes the
    sizes file + manifest. Shard files are raw C-order rows — exactly
    what ``np.memmap`` + ``ft_gather_rows`` read back."""

    def __init__(self, store_dir: str, *, n_max: int,
                 x_feat: Tuple[int, ...], y_feat: Tuple[int, ...],
                 x_dtype, y_dtype, clients_per_shard: int = 65536):
        if clients_per_shard < 1:
            raise ValueError("clients_per_shard must be >= 1")
        if clients_per_shard * n_max > np.iinfo(np.int32).max:
            raise ValueError(
                f"clients_per_shard * n_max ({clients_per_shard} * "
                f"{n_max}) overflows int32 — shrink the shard so the "
                "per-shard native gather stays legal")
        self._dir = pathlib.Path(store_dir)
        self._dir.mkdir(parents=True, exist_ok=True)
        self.n_max = int(n_max)
        self.clients_per_shard = int(clients_per_shard)
        self._feat = {"x": tuple(x_feat), "y": tuple(y_feat)}
        self._dtypes = {"x": np.dtype(x_dtype), "y": np.dtype(y_dtype)}
        self._count = 0
        self._sizes: list = []
        self._shards: dict = {"x": [], "y": []}

    def _shard_path(self, tensor: str, sid: int) -> pathlib.Path:
        return self._dir / f"{tensor}.{sid:05d}.bin"

    def append(self, x_chunk: np.ndarray, y_chunk: np.ndarray,
               sizes_chunk: np.ndarray) -> None:
        x_chunk = np.asarray(x_chunk)
        y_chunk = np.asarray(y_chunk)
        sizes_chunk = np.asarray(sizes_chunk, np.int32)
        c = x_chunk.shape[0]
        if (x_chunk.shape[:2] != (c, self.n_max)
                or y_chunk.shape[:2] != (c, self.n_max)
                or sizes_chunk.shape != (c,)):
            raise ValueError(
                f"chunk shapes disagree: x {x_chunk.shape}, "
                f"y {y_chunk.shape}, sizes {sizes_chunk.shape} "
                f"(n_max={self.n_max})")
        S = self.clients_per_shard
        pos = 0
        while pos < c:
            sid = self._count // S
            take = min(S - self._count % S, c - pos)
            for name, chunk in (("x", x_chunk), ("y", y_chunk)):
                path = self._shard_path(name, sid)
                if len(self._shards[name]) <= sid:
                    self._shards[name].append(path.name)
                part = np.ascontiguousarray(
                    chunk[pos:pos + take], dtype=self._dtypes[name])
                with open(path, "ab") as f:
                    part.tofile(f)
            self._sizes.append(sizes_chunk[pos:pos + take])
            self._count += take
            pos += take

    def finalize(self) -> pathlib.Path:
        sizes = (np.concatenate(self._sizes) if self._sizes
                 else np.zeros((0,), np.int32))
        sizes.astype(np.int32).tofile(str(self._dir / SIZES_NAME))
        manifest = {
            "format": STORE_FORMAT,
            "version": STORE_VERSION,
            "num_clients": self._count,
            "n_max": self.n_max,
            "clients_per_shard": self.clients_per_shard,
            "sizes_file": SIZES_NAME,
            "tensors": {
                name: {"dtype": self._dtypes[name].name,
                       "feat": list(self._feat[name]),
                       "shards": self._shards[name]}
                for name in ("x", "y")
            },
        }
        # write-tmp-then-replace: the manifest's presence IS the
        # store's validity marker (the loader names save_client_store
        # when it is missing), so a crash mid-write must not leave a
        # torn manifest that parses as a broken store
        mpath = self._dir / MANIFEST_NAME
        tmp = self._dir / (MANIFEST_NAME + ".tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=1, sort_keys=True)
        os.replace(tmp, mpath)
        return mpath


def save_client_store(store_dir: str, data: ClientData,
                      clients_per_shard: int = 65536,
                      chunk_clients: int = 4096) -> pathlib.Path:
    """Materialize a :class:`ClientData` to the sharded on-disk layout
    :class:`MmapClientStore` reads. Convenience wrapper over
    :class:`MmapStoreWriter` (which populations too big for RAM should
    drive directly, chunk by chunk)."""
    x = np.asarray(data.x)
    y = np.asarray(data.y)
    sizes = np.asarray(data.sizes, np.int32)
    writer = MmapStoreWriter(
        store_dir, n_max=x.shape[1], x_feat=x.shape[2:],
        y_feat=y.shape[2:], x_dtype=x.dtype, y_dtype=y.dtype,
        clients_per_shard=clients_per_shard)
    for lo in range(0, x.shape[0], chunk_clients):
        hi = lo + chunk_clients
        writer.append(x[lo:hi], y[lo:hi], sizes[lo:hi])
    return writer.finalize()


def _cpu_device():
    """The CPU backend device for schedule replay, or None when the
    platform list excludes it (JAX_PLATFORMS=tpu): the schedule is a
    few-KB computation, so falling back to the default device is
    correct, just not free."""
    try:
        return jax.devices("cpu")[0]
    except RuntimeError:
        return None


def _cpu_scope(device):
    """Dispatch scope pinning a host replay's jitted draws to the CPU
    backend (identity scope when the platform is unavailable —
    single-backend builds). One definition for every host-replay twin:
    :class:`RoundSchedule` here and the async plane's scheduler / row
    plan (``async_plane/scheduler.py``, ``async_plane/commit.py``)."""
    return jax.default_device(device) if device is not None \
        else contextlib.nullcontext()


class RoundSchedule:
    """Host replica of the round program's index schedule.

    Given the server PRNG key (its raw ``key_data``) and a round
    number, reproduces EXACTLY the ``(idx, rows)`` the device round
    program would derive: the same ``fold_in``/``split`` chain, the
    same ``participation_indices`` (in the same ``participation_mode``
    — 'perm' or the O(k) 'sparse' draw), the same ``round_row_plan`` —
    threefry is backend-deterministic and ``argsort`` is stable, so
    the CPU-backend replay is bit-exact. One jitted schedule function
    (static shapes) serves every round; it traces once.

    ``layout='shard'`` (the full-loss feed plan, qFFL) replays only
    participation: the feed carries whole shards and row selection
    happens in-program, exactly like the device shard gather.
    ``probe_fn`` (DRFA's dual phase — the algorithm's
    ``host_probe_fn``) extends the replay with the post-round probe
    plan ``(probe_idx, probe_rows)`` drawn from the SAME
    ``fold_in(rng_round, 99)`` chain the device post hook consumes."""

    def __init__(self, key_data: np.ndarray, key_impl, num_clients: int,
                 k_online: int, num_rows: int, n_max: int,
                 sizes: np.ndarray, participation_mode: str = "perm",
                 participation_fn: Optional[Callable] = None,
                 probe_fn: Optional[Callable] = None,
                 layout: str = "batch"):
        # lazy import: parallel.federated imports this module at load
        from fedtorch_tpu.parallel.federated import participation_indices

        self._cpu = _cpu_device()
        sizes = np.asarray(sizes, np.int32)

        def sched(key, round_idx):
            rng_round = jax.random.fold_in(key, round_idx)
            rng_sample, rng_train = jax.random.split(rng_round)
            if participation_fn is not None:
                idx = participation_fn(rng_sample, round_idx)
            else:
                idx = participation_indices(
                    rng_sample, num_clients, k_online, round_idx,
                    mode=participation_mode)
            if layout == "shard":
                # whole shards: the in-program epoch_permutation does
                # row selection, so the replay stops at participation
                rows = None
            else:
                on_sizes = jnp.take(jnp.asarray(sizes), idx)
                rngs = jax.random.split(rng_train, k_online)
                rows = jax.vmap(lambda r, s: round_row_plan(
                    r, s, n_max, num_rows))(rngs, on_sizes)
            if probe_fn is None:
                return idx, rows
            return (idx, rows) + tuple(probe_fn(rng_round))

        with self._scope():
            self._key = jax.random.wrap_key_data(
                jnp.asarray(np.asarray(key_data)), impl=key_impl)
            # the key input is REUSED by every round's replay
            # (donation would invalidate it); outputs are a few KB
            # lint: disable=FTL004 — inputs reused every round
            self._jit = jax.jit(sched)

    def _scope(self):
        return _cpu_scope(self._cpu)

    def __call__(self, round_idx: int):
        """``(idx, rows[, probe_idx, probe_rows])`` as numpy — the one
        blocking fetch of the streaming plane, and it blocks on the
        CPU backend's stream, not the accelerator's."""
        with self._scope():
            out = self._jit(self._key, np.int32(round_idx))
            return jax.device_get(out)


class StreamFeedProducer:
    """The round-ahead feed pipeline: schedule replay → native row
    gather → async ``device_put``, all on a background thread
    (:class:`HostPrefetcher`, depth = the double buffer), so round
    r+1's feed is built and its transfer dispatched while round r
    computes. ``place_fn`` is the trainer's sharding-aware placement
    (replicated over the mesh; multihost-safe via ``mesh._put``).

    The producer is keyed by an abstract monotone STEP LABEL, not a
    round index per se: the default plan replays the synchronous round
    schedule (:class:`RoundSchedule`, label = round index), while the
    async commit plane passes ``plan_fn`` and the label is the COMMIT
    VERSION (its deterministic event scheduler decides which clients'
    rows each commit consumes — async_plane/commit.py). ``plan_fn(step)
    -> (label, idx, rows, extras)``; a non-None ``extras`` pytree is
    placed on device alongside the feed and handed back with it.

    Feeds are strictly sequential from ``start_round``; a consumer that
    observes a label mismatch (host state rewritten out from under the
    producer — supervisor rollback, resume) must discard the producer
    (``FederatedTrainer.invalidate_stream``) rather than reorder."""

    def __init__(self, store: ClientStore, *, batch_size: int,
                 start_round: int, key_data=None, key_impl=None,
                 num_clients: Optional[int] = None,
                 k_online: Optional[int] = None,
                 local_steps: Optional[int] = None,
                 place_fn: Optional[Callable] = None, depth: int = 2,
                 timeout_s: float = 120.0,
                 plan_fn: Optional[Callable] = None, window: int = 0,
                 participation_mode: str = "perm",
                 participation_fn: Optional[Callable] = None,
                 probe_fn: Optional[Callable] = None,
                 feed_layout: str = "batch",
                 cohort_rows: Optional[Tuple[int, int]] = None):
        self.store = store
        self.start_round = int(start_round)
        self.batch_size = batch_size
        # pod-scale per-host packing (docs/multihost.md): when the
        # trainer shards the client axis, this host's producer packs
        # ONLY cohort rows [lo, hi) — per-host gather work, H2D bytes
        # and feed RAM shrink by the shard count. idx/sizes stay the
        # FULL [k] cohort (every shard needs the global weighting /
        # scatter metadata); only the row tensors are local.
        if cohort_rows is not None:
            lo, hi = int(cohort_rows[0]), int(cohort_rows[1])
            if not 0 <= lo < hi:
                raise ValueError(
                    f"cohort_rows must be a [lo, hi) block with "
                    f"0 <= lo < hi, got {cohort_rows!r}")
            cohort_rows = (lo, hi)
        self._cohort_rows = cohort_rows
        self.shard_pack_s = 0.0  # producer: local-block pack wall
        self._place = place_fn if place_fn is not None else jax.device_put
        self._timeout_s = timeout_s
        self._plan_fn = plan_fn
        if feed_layout not in ("batch", "shard"):
            raise ValueError(
                f"feed_layout must be 'batch' or 'shard', "
                f"got {feed_layout!r}")
        self.feed_layout = feed_layout
        # window >= 1 is the SCANNED STREAMED program's producer
        # (parallel/round_program.py): each produced item packs
        # ``window`` consecutive rounds' feeds stacked on a leading
        # [R] axis (R == 1 included — the scan still wants its leading
        # axis), so the device can lax.scan window r while this thread
        # builds window r+1 — the feed's label is the window's FIRST
        # round and consumption advances by ``window`` rounds per pop.
        # window == 0 (default) is the per-round producer: flat feeds,
        # one per round. plan_fn producers (the async commit plane)
        # stay per-commit: a commit is already a one-step program.
        self.window = int(window)
        if self.window < 0:
            raise ValueError(f"window must be >= 0, got {window}")
        if plan_fn is not None and self.window != 0:
            raise ValueError(
                "plan_fn producers (the async commit plane) produce "
                "one feed per commit; feed windows are the scanned "
                "round schedule's (window must be 0 with plan_fn)")
        # rounds consumed per pop (a flat feed covers one round)
        self._stride = max(self.window, 1)
        if plan_fn is None:
            self.feed_rows = (store.n_max if feed_layout == "shard"
                              else local_steps * batch_size)
            self._schedule = RoundSchedule(
                key_data, key_impl, num_clients, k_online,
                self.feed_rows, store.n_max, store.sizes,
                participation_mode=participation_mode,
                participation_fn=participation_fn,
                probe_fn=probe_fn, layout=feed_layout)
        else:
            self._schedule = None
        self._expected = self.start_round
        self.rounds_produced = 0
        # host-side gauges (fedtorch_tpu.telemetry; all seconds except
        # the counts): what used to die in thread-local variables
        self.gather_s = 0.0   # producer: schedule replay + row pack
        self.h2d_s = 0.0      # producer: device_put DISPATCH wall
        self.wait_s = 0.0     # consumer: blocked on the feed queue
        self._prefetcher = HostPrefetcher(self._produce, depth=depth,
                                          name="stream-feed-producer")

    def _pack_feed(self, idx, rows, probe=None) -> RoundFeed:
        """One gather attempt, with the 'stream.delay'/'stream.gather'
        host-chaos seams inside the retried closure — each retry
        re-draws the injector, and a REAL transient gather error (an
        mmap read hiccup on the disk-backed store) takes the same
        bounded-retry path. Pure over (idx, rows, probe), so retries
        are exact replays.

        Under pod-scale sharding (``cohort_rows``) only this host's
        [lo, hi) client block is gathered; the returned feed's
        ``idx``/``sizes`` are restored to the full cohort so the
        device program's weighting and scatter seams see global
        metadata while x/y/pre_x/pre_y hold k/S rows."""
        def attempt():
            host_chaos.maybe_delay("stream.delay")
            host_chaos.maybe_raise("stream.gather")
            t0 = time.perf_counter()
            cr = self._cohort_rows
            if cr is None:
                pidx, prows = idx, rows
            else:
                pidx = np.asarray(idx)[cr[0]:cr[1]]
                prows = (None if rows is None
                         else np.asarray(rows)[cr[0]:cr[1]])
            if prows is None:
                feed = self.store.pack_shards(pidx, self.batch_size)
            else:
                feed = self.store.pack(pidx, prows, self.batch_size)
            if cr is not None:
                full = np.asarray(idx, np.int64)
                feed = feed._replace(
                    idx=full.astype(np.int32),
                    sizes=self.store.sizes[full])
                self.shard_pack_s += time.perf_counter() - t0
            if probe is not None:
                qi, qx, qy = self.store.pack_probe(*probe)
                feed = feed._replace(probe_idx=qi, probe_x=qx,
                                     probe_y=qy)
            return feed
        return host_recovery.retry(attempt, "stream.gather")

    def _pack_window(self, idxs, rowss, probes=None) -> RoundFeed:
        """The window twin of :meth:`_pack_feed`: same chaos seams,
        same bounded retry, one flat gather for the whole window
        (per-round probe packs stack on the leading [R] axis)."""
        def attempt():
            host_chaos.maybe_delay("stream.delay")
            host_chaos.maybe_raise("stream.gather")
            t0 = time.perf_counter()
            cr = self._cohort_rows
            if cr is None:
                feed = self.store.pack_window(idxs, rowss,
                                              self.batch_size)
            else:
                # slice the CLIENT axis (axis 1 of [R, k, ...]); the
                # full [R, k] idx/sizes come back below
                feed = self.store.pack_window(
                    np.asarray(idxs)[:, cr[0]:cr[1]],
                    np.asarray(rowss)[:, cr[0]:cr[1]],
                    self.batch_size)
                full = np.asarray(idxs, np.int64)
                feed = feed._replace(
                    idx=full.astype(np.int32),
                    sizes=self.store.sizes[full])
                self.shard_pack_s += time.perf_counter() - t0
            if probes is not None:
                packed = [self.store.pack_probe(*p) for p in probes]
                feed = feed._replace(
                    probe_idx=np.stack([p[0] for p in packed]),
                    probe_x=np.stack([p[1] for p in packed]),
                    probe_y=np.stack([p[2] for p in packed]))
            return feed
        return host_recovery.retry(attempt, "stream.gather")

    def _place_feed(self, feed, extras):
        """The device_put dispatch attempt ('stream.h2d' seam):
        re-placing a host feed is idempotent (another transfer of the
        same bytes), so a failed dispatch retries bounded too."""
        def attempt():
            host_chaos.maybe_raise("stream.h2d")
            return self._place(feed if extras is None else
                               (feed, extras))
        return host_recovery.retry(attempt, "stream.h2d")

    def _produce(self, step: int):
        t0 = time.perf_counter()
        with telemetry.span("stream.gather", step=step):
            if self._plan_fn is not None:
                label, idx, rows, extras = self._plan_fn(step)
                feed = self._pack_feed(idx, rows)
            elif self.window == 0:
                label = self.start_round + step
                plan = self._schedule(label)
                extras = None
                feed = self._pack_feed(
                    plan[0], plan[1],
                    probe=plan[2:] if len(plan) > 2 else None)
            else:
                # scanned-stream window: replay `window` consecutive
                # rounds' index plans, then ONE flat gather packs the
                # whole [R, k, K*B, ...] window (pack_window — no
                # per-round feeds + stack copy; host residency: one
                # window; the device holds at most depth+1 windows)
                label = self.start_round + step * self.window
                extras = None
                plans = [self._schedule(label + j)
                         for j in range(self.window)]
                idxs = np.stack([p[0] for p in plans])
                rowss = np.stack([p[1] for p in plans])
                probes = ([p[2:] for p in plans]
                          if len(plans[0]) > 2 else None)
                feed = self._pack_window(idxs, rowss, probes)
        t1 = time.perf_counter()
        # device_put dispatches the H2D copy and returns immediately —
        # the transfer rides behind the in-flight round's compute (so
        # this span is DISPATCH cost; the transfer itself shows up on
        # the device timeline of a profiler capture)
        with telemetry.span("stream.h2d_dispatch", round=label):
            placed = self._place_feed(feed, extras)
        self.gather_s += t1 - t0
        self.h2d_s += time.perf_counter() - t1
        # a feed window counts as its width in rounds (the gauge is
        # rounds of data produced, not queue items)
        self.rounds_produced += self._stride
        return label, placed

    def next_feed(self) -> RoundFeed:
        t0 = time.perf_counter()
        with telemetry.span("stream.wait", round=self._expected):
            round_idx, feed = self._prefetcher.next(
                timeout=self._timeout_s)
        self.wait_s += time.perf_counter() - t0
        if round_idx != self._expected:
            # close BEFORE raising: the failed run must not leak a
            # daemon producer thread still filling the queue and
            # pinning device feed buffers (the consumer is abandoning
            # this producer — nothing will ever drain it)
            self.close()
            raise RuntimeError(
                f"stream feed for round {round_idx} but round "
                f"{self._expected} expected — the producer desynced "
                "from the training state (rollback/resume without "
                "invalidate_stream?)")
        # a window advances the round cursor by its full width
        self._expected += self._stride
        return feed

    def alive(self) -> bool:
        """Producer-thread liveness (the prefetcher's)."""
        return self._prefetcher.alive()

    def stats(self) -> dict:
        """Host gauges for the telemetry round row: prefetch depth at
        call time, cumulative producer gather/H2D-dispatch wall,
        cumulative consumer wait, and the client store's residency
        split (resident RAM vs mmap-addressable — the million-client
        evidence that host residency is O(feed), not O(C)). A steadily
        positive ``wait_s`` delta with depth 0 means the producer is
        the round clock — the input-stall signal tf.data's
        instrumentation exists to surface (Murray et al. 2021)."""
        # monotone float accumulators, producer-written/consumer-read:
        # each is one GIL-atomic store per round, and a momentarily
        # stale gauge in a once-per-round telemetry snapshot is
        # harmless — a lock here would serialize the producer's hot
        # loop against the round-row emit for no observable gain
        out = {
            "stream_depth": float(self._prefetcher.depth()),
            "stream_wait_s": self.wait_s,
            "stream_gather_s": self.gather_s,  # lint: disable=FTH003 — GIL-atomic monotone gauges; staleness is bounded by one round
            "stream_h2d_s": self.h2d_s,  # lint: disable=FTH003 — GIL-atomic monotone gauges; staleness is bounded by one round
            "stream_produced": float(self.rounds_produced),
            "stream_store_resident_mb":
                float(self.store.resident_nbytes) / 1e6,
            "stream_store_mapped_mb":
                float(self.store.mapped_nbytes) / 1e6,
        }
        if self._cohort_rows is not None:
            # pod-scale packing: this host's cohort block width and
            # its cumulative local pack wall — the per-shard producer
            # evidence (docs/performance.md "Pod-scale round programs")
            lo, hi = self._cohort_rows
            out["stream_shard_rows"] = float(hi - lo)
            out["stream_shard_pack_s"] = self.shard_pack_s  # lint: disable=FTH003 — GIL-atomic monotone gauge; staleness is bounded by one round
        return out

    def close(self) -> bool:
        """Stop the producer; True when the thread verifiably exited
        (see ``HostPrefetcher.close``)."""
        return self._prefetcher.close()
