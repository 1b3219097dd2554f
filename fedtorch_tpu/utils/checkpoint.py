"""Checkpoint / resume.

Parity with ``logs/checkpoint.py`` — and one deliberate upgrade: the
reference checkpoints only the server's aggregated model (:68-82), losing
client aux state (control variates, error-feedback memory, personal
models, dual variables) on resume (SURVEY.md §5.4). Here the FULL round
state pytree — ServerState + ClientState, including the threaded PRNG key
and round counter — is serialized, so a resumed run continues exactly.

* run-folder naming from hyperparams + timestamp
  (get_checkpoint_folder_name, checkpoint.py:12-45);
* best-accuracy copy (``model_best``: a hard link to the checkpoint
  just written, a written copy where the file system has no links) and
  optional per-round keeps, files of their own (save_some_models,
  checkpoint.py:68-82);
* resume with config-compatibility validation (same dataset/batch size,
  new num_epochs >= old — checkpoint.py:93-139).
"""
from __future__ import annotations

import atexit
import contextlib
import dataclasses
import hashlib
import json
import os
import re
import time
import warnings

import numpy as np
from typing import Optional, Tuple

import jax
import msgpack
from flax import serialization

from fedtorch_tpu import telemetry
from fedtorch_tpu.config import ExperimentConfig
from fedtorch_tpu.telemetry import faults as _tel_faults


def get_checkpoint_folder_name(cfg: ExperimentConfig) -> str:
    """Hyperparam-encoding run directory name (checkpoint.py:12-45)."""
    fed = cfg.federated
    parts = [
        time.strftime("%Y-%m-%d_%H-%M-%S"),
        f"l2-{cfg.optim.weight_decay}",
        f"lr-{cfg.optim.lr}",
        f"momentum-{cfg.optim.in_momentum_factor}",
        f"batchsize-{cfg.data.batch_size}",
        f"arch-{cfg.model.arch}",
        f"data-{cfg.data.dataset}",
    ]
    if fed.federated:
        parts += [f"alg-{cfg.effective_algorithm}",
                  f"clients-{fed.num_clients}",
                  f"rate-{fed.online_client_rate}"]
    return "_".join(parts)


def init_checkpoint_dir(cfg: ExperimentConfig) -> str:
    """Run directory. ``checkpoint.run_dir`` (when set) is used EXACTLY
    — no hyperparam/timestamp subfolders — because an elastically
    restarted process must land in the same directory as the attempt
    it is resuming (robustness/harness.py relaunches with
    ``--resume <this dir>``)."""
    if cfg.checkpoint.run_dir:
        os.makedirs(cfg.checkpoint.run_dir, exist_ok=True)
        return cfg.checkpoint.run_dir
    root = os.path.join(cfg.checkpoint.checkpoint_dir, cfg.data.dataset,
                        cfg.model.arch, get_checkpoint_folder_name(cfg))
    os.makedirs(root, exist_ok=True)
    return root


def _compat_meta(cfg: ExperimentConfig) -> dict:
    return {
        "dataset": cfg.data.dataset,
        "batch_size": cfg.data.batch_size,
        "arch": cfg.model.arch,
        "num_epochs": cfg.train.num_epochs,
        "algorithm": cfg.effective_algorithm,
        "num_clients": cfg.federated.num_clients,
        # the async plane wraps server.aux with the snapshot ring, so a
        # sync/async mismatch is a STRUCTURAL incompatibility (it would
        # otherwise surface as a silent corrupt-skip fresh start)
        "sync_mode": cfg.federated.sync_mode,
        # norm_bound robust aggregation wraps server.aux with its
        # momentum tree — the same structural-mismatch class. Stored
        # as a bool (not the rule name) so e.g. mean <-> median resume,
        # which shares the aux structure, stays legal.
        "robust_momentum": cfg.fault.robust_agg == "norm_bound",
        # the DP stage wraps server.aux with its traced noise scale
        # (robustness/privacy.py) — the same structural-mismatch class
        "dp_aggregation": cfg.fault.dp_armed,
    }


def _unkey(server):
    """Typed PRNG keys are not serializable; carry the raw key data."""
    return server._replace(rng=jax.random.key_data(server.rng))


def _rekey(server):
    return server._replace(rng=jax.random.wrap_key_data(server.rng))


def _strip_padding(clients, num_clients: int):
    """Only the REAL client range is serialized: the padding tail
    (pad_client_axis) depends on the device count of the run that wrote
    the checkpoint, so keeping it would pin restores to that topology."""
    return jax.tree.map(lambda x: x[:num_clients], clients)


def _owning_host_copy(x):
    """An OWNING host array: on the CPU backend ``device_get`` can hand
    back zero-copy VIEWS of device buffers, and the round jit donates
    those buffers (federated.py donate_argnums) — an aliased snapshot
    would race with the next round's dispatch. Arrays that already own
    their data (the TPU device_get result) pass through uncopied."""
    if isinstance(x, np.ndarray) and x.flags["OWNDATA"]:
        return x
    return np.array(x, copy=True)


def _snapshot(server, clients, cfg: ExperimentConfig):
    """Device -> host copy of the serializable round state. Blocks
    until the state is materialized (so the snapshot is consistent),
    after which serialization/IO can proceed off-thread.

    Multi-host: client state is SHARDED across processes
    (shard_clients), so a plain device_get on one process would touch
    non-addressable shards; the cross-host allgather materializes the
    global value on every process. It is a COLLECTIVE — every process
    must call _snapshot even though only process 0 writes.

    The one part of a save that needs the device's state still, hence
    the floor of a save taken off the loop: it runs under its own span,
    ``checkpoint.snapshot``, for both savers."""
    def to_host(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # sharded across processes (the client axis): collective
            # gather of the GLOBAL value
            from jax.experimental import multihost_utils
            return multihost_utils.process_allgather(x, tiled=True)
        return jax.device_get(x)

    with telemetry.span("checkpoint.snapshot") as sp:
        state = {"server": _unkey(server),
                 "clients": _strip_padding(clients,
                                           cfg.federated.num_clients)}
        fetched = jax.tree.leaves(jax.tree.map(to_host, state))
        owned = [_owning_host_copy(x) for x in fetched]
        sp.note(bytes=sum(x.nbytes for x in owned), leaves=len(owned),
                owned_copy_bytes=sum(
                    o.nbytes for x, o in zip(fetched, owned) if o is not x))
    return jax.tree.unflatten(jax.tree.structure(state), owned)


# self-describing checkpoint framing: magic + payload length + sha256
# prepended to the flax payload in the SAME file, so the integrity
# record can never go stale relative to its payload (a cross-file
# record — e.g. in checkpoint.json — has a crash window between the two
# atomic writes, and describes only the latest checkpoint, not the
# per-round keeps). Legacy unframed checkpoints are still readable.
_CKPT_MAGIC = b"FTCK1\x00"
# frame layout: magic | 8-byte big-endian payload length | sha256 |
# payload — offsets derived from the magic so every parser (framing,
# resume verification, the GC quick-probe) reads the same layout
_CKPT_LEN_OFF = len(_CKPT_MAGIC)
_CKPT_DIGEST_OFF = _CKPT_LEN_OFF + 8
_CKPT_HEADER = _CKPT_DIGEST_OFF + 32


def _bin_header(n: int) -> bytes:
    """msgpack's header of a ``bin`` of ``n`` bytes (bin 8 / 16 / 32,
    the narrowest that holds ``n``, as its packer chooses)."""
    if n <= 0xff:
        return b"\xc4" + n.to_bytes(1, "big")
    if n <= 0xffff:
        return b"\xc5" + n.to_bytes(2, "big")
    return b"\xc6" + n.to_bytes(4, "big")


def _ext_header(code: int, n: int) -> bytes:
    """msgpack's header of an ext of type ``code`` and ``n`` data
    bytes: fixext for 1, 2, 4, 8 and 16, else ext 8 / 16 / 32."""
    fix = {1: b"\xd4", 2: b"\xd5", 4: b"\xd6", 8: b"\xd7", 16: b"\xd8"}
    if n in fix:
        head = fix[n]
    elif n <= 0xff:
        head = b"\xc7" + n.to_bytes(1, "big")
    elif n <= 0xffff:
        head = b"\xc8" + n.to_bytes(2, "big")
    else:
        head = b"\xc9" + n.to_bytes(4, "big")
    return head + code.to_bytes(1, "big")


def _plain_array(x) -> bool:
    """A leaf whose C-order bytes can go into the file as they lie in
    memory: an array of plain items, inside msgpack's limit. (The
    zero-size leaf has no memory to view and packs in a few bytes.)"""
    return (isinstance(x, np.ndarray) and not x.dtype.hasobject
            and x.dtype.fields is None
            and 0 < x.nbytes <= serialization.MAX_CHUNK_SIZE)


def _payload_pieces(host_state) -> Tuple[list, dict]:
    """The msgpack payload of ``host_state`` as a list of buffers whose
    concatenation is byte for byte ``serialization.to_bytes(host_state)``
    — never built as one object, where flax copies every leaf three
    times (``tobytes``, the inner ``packb``, the outer ``packb``'s
    growing buffer). A ``_plain_array`` leaf contributes its thirty-odd
    bytes of msgpack text (ext header, the array of shape, dtype name
    and bin header) and then memory that is already there, as a
    ``uint8`` memoryview (bfloat16 and the other extension dtypes
    refuse the buffer protocol under their own type): ITS OWN where it
    is C-contiguous, else one C-order copy of it. The second case is
    the chip's: a TPU keeps the per-client state with the client axis
    minor-most and ``device_get`` hands the host the same strides, so
    the file's C-order bytes exist nowhere until something lays them
    out. Every other value goes through flax's own packer, chunked
    where flax chunks. The views of the first kind borrow the snapshot:
    it must outlive the write, and does (the caller, or the async
    worker's job, holds it).

    Returns (pieces, counts) of leaf bytes by the way they took:
    ``borrowed_bytes`` views of the snapshot's own memory,
    ``relaid_bytes`` copied once into C order, ``copied_bytes`` packed
    by flax's packer (the tree's map headers and keys, and an array's
    header, count as none of them); and ``relaid_s``, the seconds
    inside those C-order copies (two clock reads a strided leaf)."""
    # the two packers flax's msgpack_serialize and _ndarray_to_bytes use
    outer = msgpack.Packer(default=serialization._msgpack_ext_pack,
                           strict_types=True)
    inner = msgpack.Packer(use_bin_type=True)
    ndarray_code = int(serialization._MsgpackExtType.ndarray)
    pieces: list = []
    text = bytearray()          # small bytes since the last view
    counts = {"borrowed_bytes": 0, "relaid_bytes": 0, "copied_bytes": 0,
              "relaid_s": 0.0}

    def walk(node):
        if type(node) is dict:
            text.extend(outer.pack_map_header(len(node)))
            for key, value in node.items():
                text.extend(outer.pack(key))
                walk(value)
            return
        if isinstance(node, jax.Array):     # as _np_convert_in_place
            node = np.array(node)
        if not _plain_array(node):
            if isinstance(node, np.ndarray) \
                    and node.nbytes > serialization.MAX_CHUNK_SIZE:
                node = serialization._chunk(node)
            packed = outer.pack(node)
            counts["copied_bytes"] += len(packed)
            text.extend(packed)
            return
        if node.flags.c_contiguous:
            counts["borrowed_bytes"] += node.nbytes
        else:
            t0 = time.perf_counter()
            node = np.ascontiguousarray(node)
            counts["relaid_s"] += time.perf_counter() - t0
            counts["relaid_bytes"] += node.nbytes
        head = (inner.pack_array_header(3) + inner.pack(node.shape)
                + inner.pack(node.dtype.name) + _bin_header(node.nbytes))
        text.extend(_ext_header(ndarray_code, len(head) + node.nbytes))
        text.extend(head)
        pieces.append(bytes(text))
        text.clear()
        pieces.append(memoryview(node.reshape(-1).view(np.uint8)))

    walk(serialization.to_state_dict(host_state))
    if text:
        pieces.append(bytes(text))
    return pieces, counts


def _frame_header(pieces) -> bytes:
    """The frame's header for the payload that ``pieces`` (buffers, in
    order) make up: its length, and its sha256 fed piece by piece."""
    digest = hashlib.sha256()
    for piece in pieces:
        digest.update(piece)
    return (_CKPT_MAGIC + sum(len(p) for p in pieces).to_bytes(8, "big")
            + digest.digest())


def _frame_payload(payload: bytes) -> bytes:
    """The frame as one object. The writer never builds it (nor the
    payload): it writes the header and the payload's pieces in turn."""
    return _frame_header((payload,)) + payload


def _frame_want_len(head: bytes) -> int:
    """The payload length a frame header claims (``head`` must hold at
    least ``_CKPT_HEADER`` bytes)."""
    return int.from_bytes(head[_CKPT_LEN_OFF:_CKPT_DIGEST_OFF], "big")


def _unframe_payload(blob: bytes):
    """Returns (payload, why_corrupt). ``why_corrupt`` is None for a
    verified frame AND for legacy unframed blobs (no record to check —
    deserialization is their only guard)."""
    if not blob.startswith(_CKPT_MAGIC):
        return blob, None
    if len(blob) < _CKPT_HEADER:
        return None, "truncated header"
    want_len = _frame_want_len(blob)
    digest = blob[_CKPT_DIGEST_OFF:_CKPT_HEADER]
    payload = blob[_CKPT_HEADER:]
    if len(payload) != want_len:
        return None, (f"{len(payload)} payload bytes on disk, expected "
                      f"{want_len} (truncated write?)")
    if hashlib.sha256(payload).digest() != digest:
        return None, "sha256 mismatch (bit rot or torn write)"
    return payload, None


def _atomic_write(path: str, *parts) -> None:
    """tmp + fsync + rename so a crash (including power loss — without
    the fsync, delayed allocation could rename before the data blocks
    hit disk) never corrupts the previous checkpoint. The reference
    overwrites in place (checkpoint.py:72). The file holds ``parts``
    (bytes or memoryviews) one after the other.

    Self-healing (docs/robustness.md "Host plane"): each write runs
    under the bounded 'ckpt.write' retry policy — a transient
    ``OSError`` (ENOSPC racing a log rotation, an NFS hiccup, the
    injected drill fault) is retried with backoff instead of aborting
    the run; exhaustion raises a seam-named error."""
    # lazy imports: utils.__init__ is imported by the robustness
    # package chain, so a module-level robustness import here would
    # be circular
    from fedtorch_tpu.robustness import host_chaos, host_recovery
    attempts = 0

    def attempt():
        # one set of sub-spans an attempt: a retried write shows as two
        nonlocal attempts
        attempts += 1
        tmp = path + ".tmp"
        with contextlib.ExitStack() as closing:
            with telemetry.span("checkpoint.file_write.data"):
                host_chaos.maybe_raise_io("ckpt.write")
                f = closing.enter_context(open(tmp, "wb"))
                for part in parts:
                    f.write(part)
                f.flush()
            with telemetry.span("checkpoint.file_write.fsync"):
                os.fsync(f.fileno())
        with telemetry.span("checkpoint.file_write.rename"):
            os.replace(tmp, path)
    with telemetry.span("checkpoint.file_write",
                        name=os.path.basename(path),
                        bytes=sum(len(p) for p in parts)) as sp:
        try:
            host_recovery.retry_io(attempt, "ckpt.write")
        finally:
            sp.note(attempts=attempts)


def _write_frame(path: str, frame: list) -> None:
    """One payload file of its own: ``frame`` = the header and the
    payload's pieces, written in turn, fsynced and renamed. The
    'ckpt.torn' drill seam truncates individual payload writes (each
    file written here draws independently) but lets the rename land —
    the torn frame the integrity record exists to catch at resume/GC
    time. The cut falls where it falls in the list: whole pieces
    before it, a slice of the piece it is in."""
    from fedtorch_tpu.robustness import host_chaos  # lazy: see above
    lands = host_chaos.torn_length("ckpt.torn",
                                   sum(len(p) for p in frame))
    parts = []
    for piece in frame:
        if lands <= 0:
            break
        parts.append(piece[:lands])
        lands -= len(piece)
    _atomic_write(path, *parts)


def _link_or_write(src: str, path: str, frame: list) -> None:
    """Give ``path`` the durable bytes of ``src``, the payload file
    just written, fsynced and renamed in the same directory: a hard
    link under a tmp name, renamed over ``path``. No checkpoint is ever
    written in place (the next save renames a NEW inode over ``src``),
    so ``path`` keeps these bytes for as long as it keeps its name.
    Where the file system refuses the link (any ``OSError`` of
    ``os.link``: EPERM, ENOTSUP, EMLINK or EXDEV on FUSE and
    object-store mounts), ``path`` is written as a file of its own."""
    from fedtorch_tpu.robustness import host_chaos, host_recovery

    def attempt() -> bool:
        host_chaos.maybe_raise_io("ckpt.write")
        tmp = path + ".tmp"
        try:  # a crashed writer's leftover would fail the link (EEXIST)
            os.remove(tmp)
        except FileNotFoundError:
            pass
        try:
            os.link(src, tmp)
        except OSError:
            return False
        os.replace(tmp, path)
        return True
    with telemetry.span("checkpoint.link",
                        name=os.path.basename(path)) as sp:
        linked = host_recovery.retry_io(attempt, "ckpt.write")
        sp.note(fallback=not linked)
    if not linked:
        _write_frame(path, frame)


_ROUND_KEEP_RE = re.compile(r"^checkpoint_r(\d+)\.ckpt$")


def _frame_probe(path: str):
    """Tri-state header probe: True = frame (or legacy blob) looks
    intact, False = CONFIRMED torn (size disagrees with the in-frame
    length), None = could not read — a transient probe error (the NFS
    hiccup class the write seams retry) must be treated as "don't
    know", never as "torn": deleting a keep on a read blip would
    destroy the very frame the retention exists to protect."""
    try:
        size = os.path.getsize(path)
        with open(path, "rb") as f:
            head = f.read(_CKPT_HEADER)
    except OSError:
        return None
    if len(head) < len(_CKPT_MAGIC):
        # shorter than the magic alone: cannot be a valid frame, and
        # no real legacy msgpack checkpoint is this small either — a
        # severely torn file must not count against the retention
        # budget (it would evict the newest restorable frame)
        return False
    if not head.startswith(_CKPT_MAGIC):
        return True  # legacy unframed
    if len(head) < _CKPT_HEADER:
        return False
    return size == _CKPT_HEADER + _frame_want_len(head)


def frame_quick_ok(path: str) -> bool:
    """Cheap integrity check for GC/tests: True only when the frame
    header verifiably matches the on-disk size (or the file is a
    legacy unframed blob). Header-only read — no sha256 over the
    payload, so GC stays O(keeps), not O(bytes); resume still runs
    the full digest check."""
    return _frame_probe(path) is True


def collect_round_keeps(directory: str, keep_last_n: int) -> list:
    """Bounded retention for the per-round ``checkpoint_r{N}.ckpt``
    keeps: retain the newest ``keep_last_n`` VALID frames (by round
    number) and delete the rest — including torn frames left by a
    failed/partial write, which never count against the retention
    budget (a torn newest keep must not evict the newest frame that
    can actually restore). ``keep_last_n <= 0`` keeps everything
    (``save_all_models``' historical semantics); ``checkpoint.ckpt`` /
    ``model_best.*`` are never candidates. Returns the removed
    paths."""
    if keep_last_n <= 0:
        return []
    keeps = []
    try:
        names = os.listdir(directory)
    except OSError:
        return []
    for name in names:
        m = _ROUND_KEEP_RE.match(name)
        if m:
            keeps.append((int(m.group(1)), name))
    keeps.sort()
    probes = {name: _frame_probe(os.path.join(directory, name))
              for _, name in keeps}
    valid = [name for _, name in keeps if probes[name] is True]
    retained = set(valid[max(len(valid) - keep_last_n, 0):])
    removed = []
    for _, name in keeps:
        if name in retained or probes[name] is None:
            # None = the probe could not read the file (transient
            # error): neither a retention candidate nor deletable —
            # leave it for a later GC pass to classify
            continue
        path = os.path.join(directory, name)
        try:
            os.remove(path)
            removed.append(path)
        except OSError:  # raced with an external cleaner — fine
            pass
    return removed


def _write_checkpoint(directory: str, host_state, meta: dict,
                      is_best: bool, round_idx: int,
                      save_all: bool,
                      save_some_rounds: Tuple[int, ...],
                      keep_last_n: int = 0) -> str:
    """Serialize + write an already-host-resident snapshot (the worker
    half of both the sync and async paths)."""
    os.makedirs(directory, exist_ok=True)
    # framed payload: resume verifies the in-file length + digest BEFORE
    # trying to deserialize, so a torn/truncated/bit-rotted file is
    # detected cleanly instead of surfacing as an opaque msgpack error.
    # Serialized and hashed ONCE, however many names the save gets: a
    # list, so a retried write, a refused link's copy and the keep walk
    # the same pieces again
    with telemetry.span("checkpoint.serialize") as sp:
        with telemetry.span("checkpoint.layout") as layout:
            pieces, counts = _payload_pieces(host_state)
            layout.note(relaid_s=counts.pop("relaid_s"), **counts)
        with telemetry.span("checkpoint.digest", bytes=sum(
                len(p) for p in pieces)):
            frame = [_frame_header(pieces)] + pieces
        sp.note(pieces=len(pieces), **counts)
    path = os.path.join(directory, "checkpoint.ckpt")
    _write_frame(path, frame)
    meta_bytes = json.dumps(meta, default=str).encode()
    _atomic_write(os.path.join(directory, "checkpoint.json"), meta_bytes)
    if is_best:
        # the same durable bytes under a second name: a link, not a
        # second write + fsync of the payload
        _link_or_write(path, os.path.join(directory, "model_best.ckpt"),
                       frame)
        _atomic_write(os.path.join(directory, "model_best.json"),
                      meta_bytes)
    if save_all or round_idx in save_some_rounds:
        # the per-round keeps stay files of their own: maybe_resume
        # falls back to them when the latest frame is torn
        _write_frame(
            os.path.join(directory, f"checkpoint_r{round_idx}.ckpt"),
            frame)
        with telemetry.span("checkpoint.gc"):
            collect_round_keeps(directory, keep_last_n)
    return path


def _meta_for(cfg: ExperimentConfig, round_idx: int,
              best_prec1: float) -> dict:
    return {
        "arguments": _compat_meta(cfg),
        "round": round_idx,
        "best_prec1": best_prec1,
        "config": dataclasses.asdict(cfg),
    }


def _is_writer_process() -> bool:
    """Only process 0 writes (the reference's rank-0 checkpointing,
    eval.py:120-144) — after the collective snapshot every process
    holds the same gathered state, so N writers would race on the same
    files for no benefit."""
    try:
        return jax.process_index() == 0
    except Exception:
        return True


def save_checkpoint(directory: str, server, clients,
                    cfg: ExperimentConfig, best_prec1: float,
                    is_best: bool, save_all: bool = False,
                    save_some_rounds: Tuple[int, ...] = ()) -> str:
    """Serialize the full round state (checkpoint.py:68-82 semantics),
    synchronously. See :class:`AsyncCheckpointer` for the non-blocking
    variant. Every process participates in the snapshot (it is a
    collective on multi-host); only process 0 touches the disk."""
    path = os.path.join(directory, "checkpoint.ckpt")
    host_state = _snapshot(server, clients, cfg)
    if not _is_writer_process():
        return path
    round_idx = int(server.round)
    with telemetry.span("checkpoint.write", round=round_idx):
        return _write_checkpoint(
            directory, host_state,
            _meta_for(cfg, round_idx, best_prec1), is_best, round_idx,
            save_all, save_some_rounds, cfg.checkpoint.keep_last_n)


class AsyncCheckpointer:
    """Non-blocking checkpoint writer: :meth:`save` snapshots the round
    state to host memory on the caller thread (consistent by
    construction — device_get blocks until the round's arrays are
    ready), then a single worker thread serializes and atomically writes
    it, so training dispatch never waits on msgpack or disk. Bounded
    backpressure: one snapshot being written + one queued, and a third
    ``save`` builds its snapshot then blocks in the queue until the
    oldest write finishes — so host memory holds at most THREE
    host-state copies transiently. Every requested checkpoint is
    durably written — latest-wins dropping would silently lose 'best'
    copies.

    Degraded mode (docs/robustness.md "Host plane"): a background
    write that still fails after the per-write 'ckpt.write' retries
    does NOT poison the next :meth:`save` with a confusingly-attributed
    error (the pre-PR-10 behavior). The checkpointer instead emits one
    ``ckpt.degraded`` event, counts the lost write, and falls back to
    SYNCHRONOUS writes — every later ``save`` runs the write on the
    caller thread, so a persistent disk fault surfaces at the save that
    actually hit it (and a recovered disk simply keeps checkpointing,
    slower).

    Call :meth:`wait` before reading checkpoints back or at run end.
    :meth:`close` is idempotent, runs on interpreter exit as an
    ``atexit`` fallback (a code path that never reaches the CLI's
    try/finally — e.g. a library caller's own crash — must still land
    the queued checkpoint instead of silently dropping it with the
    daemon worker thread), and unregisters itself once closed."""

    def __init__(self):
        import queue
        import threading
        self._q: "queue.Queue" = queue.Queue(maxsize=1)
        self._closed = False
        # write-latency/queue gauges for the telemetry round row
        # (docs/observability.md): written by the worker thread,
        # snapshotted by stats()/save() on the caller thread — both
        # sides under _gauges, never held across IO or an emit
        self._gauges = _tel_faults.new_lock("AsyncCheckpointer._gauges")
        self.writes = 0
        self.last_write_s = 0.0
        self.total_write_s = 0.0
        # degraded-mode state: flipped by the worker on a write that
        # exhausted its retries; save() reads it on the caller thread
        self.degraded = False
        self.lost_writes = 0
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name="async-checkpointer")
        self._thread.start()
        atexit.register(self._atexit_close)

    def _worker(self):
        while True:
            # the blocking get IS the worker's idle state: close()
            # always lands the None sentinel (size-1 queue, drained
            # first), so a timeout here would only add wakeup churn
            job = self._q.get()  # lint: disable=FTH004 — close() enqueues the None sentinel; no lock held
            if job is None:
                self._q.task_done()
                return
            t0 = time.perf_counter()
            try:
                # job[4] is round_idx (the _write_checkpoint signature)
                with telemetry.span("checkpoint.write", round=job[4]):
                    _write_checkpoint(*job)
                with self._gauges:
                    self.writes += 1
            except Exception as e:
                self._note_degraded(job[4], e)
            finally:
                dt = time.perf_counter() - t0
                with self._gauges:
                    self.last_write_s = dt
                    self.total_write_s += dt
                self._q.task_done()

    def _note_degraded(self, round_idx, exc) -> None:
        """A write was durably lost: record it once, loudly, and flip
        to synchronous writes — never poison an unrelated later
        save()."""
        import sys
        # flip the state under the gauges lock, emit AFTER releasing:
        # both note_degraded and telemetry.event below can re-enter a
        # writer (the FTH002/PR 10 class)
        with self._gauges:
            self.lost_writes += 1
            first = not self.degraded
            self.degraded = True
        print(f"AsyncCheckpointer: write for round {round_idx} lost "
              f"after retries ({exc!r}); degrading to synchronous "
              "checkpoint writes", file=sys.stderr, flush=True)
        if first:
            from fedtorch_tpu.robustness import host_recovery
            host_recovery.get_active().note_degraded("ckpt.write")
        telemetry.event("ckpt.degraded", round=round_idx,
                        error=repr(exc), lost_writes=self.lost_writes)

    def stats(self) -> dict:
        """Telemetry gauges: durable writes, last/total write wall,
        how many snapshots sit queued behind the worker (a rising
        queue depth means disk is slower than the eval cadence), and
        the degraded-mode pair."""
        with self._gauges:
            return {
                "ckpt_queue_depth": float(self._q.qsize()),
                "ckpt_writes": float(self.writes),
                "ckpt_last_write_s": self.last_write_s,
                "ckpt_total_write_s": self.total_write_s,
                "ckpt_degraded": float(self.degraded),
                "ckpt_lost_writes": float(self.lost_writes),
            }

    def save(self, directory: str, server, clients,
             cfg: ExperimentConfig, best_prec1: float, is_best: bool,
             save_all: bool = False,
             save_some_rounds: Tuple[int, ...] = ()) -> None:
        # the snapshot is a COLLECTIVE on multi-host — all processes
        # take it FIRST; only process 0 writes
        host_state = _snapshot(server, clients, cfg)
        if not _is_writer_process():
            return
        round_idx = int(server.round)
        job = (directory, host_state,
               _meta_for(cfg, round_idx, best_prec1), is_best,
               round_idx, save_all, save_some_rounds,
               cfg.checkpoint.keep_last_n)
        with self._gauges:
            degraded = self.degraded
        if degraded:
            # synchronous fallback: the write happens HERE, so a
            # persistent disk fault raises at the save it actually
            # broke (honest attribution), and a recovered disk keeps
            # checkpointing without a restart. Drain the worker FIRST:
            # a job queued before degraded flipped could otherwise
            # race this thread on the same fixed .tmp names and land
            # its OLDER round after this newer one
            self._q.join()
            from fedtorch_tpu.robustness import host_recovery
            t0 = time.perf_counter()
            try:
                with telemetry.span("checkpoint.write", round=round_idx):
                    # the whole write under the seam retry: dir
                    # creation can fail with the same transient
                    # OSErrors the atomic writes can, and exhaustion
                    # must name the seam either way
                    host_recovery.retry_io(
                        lambda: _write_checkpoint(*job), "ckpt.write")
                with self._gauges:
                    self.writes += 1
            finally:
                dt = time.perf_counter() - t0
                with self._gauges:
                    self.last_write_s = dt
                    self.total_write_s += dt
            return
        self._q.put(job)

    def wait(self) -> None:
        """Block until every enqueued checkpoint is on disk (or was
        recorded lost — see ``degraded``/``lost_writes``)."""
        self._q.join()

    def close(self) -> None:
        """Drain pending writes and stop the worker. Idempotent: the
        CLI's finally block, a library caller, and the atexit fallback
        may all call it — only the first does the work (a second
        ``_q.put(None)`` after the worker exited would block forever
        on the size-1 queue)."""
        if self._closed:
            return
        self._closed = True
        atexit.unregister(self._atexit_close)
        try:
            self.wait()
        finally:
            # shut the worker down even when the drain itself raised —
            # library users must not leak the thread
            self._q.put(None)
            self._thread.join(timeout=30)

    def _atexit_close(self) -> None:
        """Interpreter-exit fallback: land the queued checkpoint, but
        never let a flush error mask the exit in progress."""
        try:
            self.close()
        except Exception as e:
            import sys
            print(f"AsyncCheckpointer: atexit flush failed: {e!r}",
                  file=sys.stderr, flush=True)


def _corrupt_skip(path: str, why: str, server, clients):
    """A corrupt/truncated checkpoint is a recoverable condition (a
    crash mid-write before the atomic rename existed, bit rot, a torn
    copy): warn and start fresh instead of dying on an opaque
    deserialization error."""
    warnings.warn(
        f"checkpoint at {path} is corrupt or truncated ({why}); "
        "skipping resume and starting from the initialized state",
        RuntimeWarning, stacklevel=3)
    return server, clients, 0.0, False


def maybe_resume(directory: Optional[str], server, clients,
                 cfg: ExperimentConfig,
                 checkpoint_index: Optional[str] = None):
    """Restore full state into freshly-initialized pytrees; validates the
    config compatibility rules of checkpoint.py:93-139. Returns
    (server, clients, best_prec1, resumed: bool).

    Corrupt or truncated checkpoints (payload length/sha256 mismatch
    against the in-file integrity frame, undecodable meta JSON, or a
    payload that fails to deserialize) are detected and SKIPPED with a
    warning — a MISSING checkpoint/meta file or config INCOMPATIBILITY
    still raises, because silently ignoring a wrong ``--resume`` target
    would be data loss."""
    if directory is None:
        return server, clients, 0.0, False
    name = "checkpoint.ckpt" if checkpoint_index is None \
        else f"checkpoint_r{checkpoint_index}.ckpt"
    path = os.path.join(directory, name)
    meta_path = os.path.join(
        directory, name.replace(".ckpt", ".json")
        if checkpoint_index is None else "checkpoint.json")
    if not os.path.exists(path):
        raise FileNotFoundError(f"No checkpoint at {path}")
    try:
        with open(meta_path) as f:
            meta = json.load(f)
    except json.JSONDecodeError as e:
        # undecodable content is corruption; a MISSING meta file is an
        # operator error and propagates as FileNotFoundError above/here.
        # Default-path self-healing (docs/robustness.md "Host plane"):
        # a torn checkpoint.json beside a healthy payload must not
        # discard the run — model_best.json carries the identical
        # compat `arguments` block, so fall back to it for validation
        # before giving up. The explicit checkpoint_index path keeps
        # the strict behavior (the operator pinned a target).
        meta = None
        if checkpoint_index is None:
            try:
                with open(os.path.join(directory, "model_best.json")) \
                        as f:
                    meta = json.load(f)
                warnings.warn(
                    f"checkpoint meta at {meta_path} is undecodable "
                    f"({e}); validated compat against model_best.json "
                    "instead", RuntimeWarning, stacklevel=2)
            except (OSError, json.JSONDecodeError):
                meta = None
        if meta is None:
            return _corrupt_skip(meta_path,
                                 f"undecodable meta JSON: {e}",
                                 server, clients)
    old = meta["arguments"]
    new = _compat_meta(cfg)
    # keys absent from older checkpoints default to the value every
    # pre-feature run had: all-sync (the only mode that existed) and no
    # norm_bound momentum wrap
    legacy_defaults = {"sync_mode": "sync", "robust_momentum": False,
                       "dp_aggregation": False}
    for key in ("dataset", "batch_size", "arch", "algorithm",
                "num_clients", "sync_mode", "robust_momentum",
                "dp_aggregation"):
        was = old.get(key, legacy_defaults[key]) \
            if key in legacy_defaults else old[key]
        if was != new[key]:
            raise ValueError(
                f"Checkpoint incompatible: {key} was {was!r}, "
                f"config has {new[key]!r} (checkpoint.py:104-120 rule)")
    if new["num_epochs"] is not None and old["num_epochs"] is not None \
            and new["num_epochs"] < old["num_epochs"]:
        raise ValueError(
            "Checkpoint incompatible: num_epochs must not shrink "
            f"({old['num_epochs']} -> {new['num_epochs']})")
    C = cfg.federated.num_clients
    with open(path, "rb") as f:
        blob = f.read()
    template = {"server": _unkey(server),
                "clients": _strip_padding(clients, C)}

    def _try_blob(raw):
        # in-file integrity frame first (cheap, precise diagnosis —
        # and valid for per-round keeps too, since every file carries
        # its own record); legacy unframed blobs fall through to the
        # deserialization try
        data, bad = _unframe_payload(raw)
        if bad is not None:
            return None, bad
        try:
            return serialization.from_bytes(template, data), None
        except Exception as e:  # msgpack/flax raise concrete types
            return None, f"deserialization failed: {e}"

    restored, why = _try_blob(blob)
    if restored is None and checkpoint_index is None:
        # self-healing fallback (docs/robustness.md "Host plane"): the
        # LATEST checkpoint is torn (a partial write that landed —
        # ENOSPC mid-replace, the 'ckpt.torn' drill), but older
        # per-round keeps may still verify. Resume from the newest
        # valid one rather than silently discarding the whole run —
        # the compat meta was already validated above, so this is the
        # same run, just an earlier durable round.
        keeps = []
        for name in os.listdir(directory):
            m = _ROUND_KEEP_RE.match(name)
            if m:
                keeps.append((int(m.group(1)), name))
        for _, name in sorted(keeps, reverse=True):
            keep_path = os.path.join(directory, name)
            try:
                with open(keep_path, "rb") as f:
                    keep_blob = f.read()
            except OSError:
                continue
            restored, keep_why = _try_blob(keep_blob)
            if restored is not None:
                warnings.warn(
                    f"checkpoint at {path} is corrupt or truncated "
                    f"({why}); resumed from the newest valid "
                    f"per-round keep {keep_path} instead",
                    RuntimeWarning, stacklevel=2)
                break
    if restored is None:
        return _corrupt_skip(path, why, server, clients)
    # from_bytes hands back numpy arrays that can be zero-copy VIEWS
    # into ``payload``; own them before anything else touches them
    restored = jax.tree.map(_owning_host_copy, restored)
    # graft the restored real clients back into the (possibly padded)
    # freshly-initialized template, preserving its sharding layout
    new_clients = jax.tree.map(lambda full, real: full.at[:C].set(real),
                               clients, restored["clients"])
    # The returned state feeds straight into the round jit, which
    # DONATES its inputs. Host-numpy leaves must not meet donation:
    # the jit's implicit numpy->Array conversion has been observed (on
    # an earlier CPU jaxlib) to hand XLA buffers whose backing memory
    # is torn down with the host array — the first post-resume round then
    # aggregates into recycled heap (bitwise-correct losses, garbage
    # server params, a heap-corruption abort at exit). Committing the
    # restored server to device arrays HERE makes resume hand back
    # exactly what init_state does — jax-owned, donation-safe buffers.
    server = jax.tree.map(
        lambda x: jax.device_put(x) if not isinstance(x, jax.Array)
        else x, _rekey(restored["server"]))
    jax.block_until_ready(server)
    return (server, new_clients,
            float(meta.get("best_prec1", 0.0)), True)
