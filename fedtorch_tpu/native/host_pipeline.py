"""ctypes bindings + background prefetcher for the native host pipeline.

Auto-compiles ``pipeline.cpp`` with g++ on first use, into a file named
after the source's content hash next to it — so a binary is only ever
loaded for the source it was built from, whatever a copy did to the
mtimes. Every entry point falls back to numpy when the toolchain or the
library is unavailable (the failure is warned about once, and
``native_available()`` reports which path runs); the gather/pad
fallbacks are bitwise-identical, ``seeded_permutation`` draws a
different stream.
"""
from __future__ import annotations

import contextlib
import ctypes
import fcntl
import glob
import hashlib
import os
import queue
import subprocess
import threading
import time
import warnings
from typing import Optional

import numpy as np

_SRC = os.path.join(os.path.dirname(__file__), "pipeline.cpp")
_LIB_STEM = os.path.join(os.path.dirname(__file__), "libfedtorch_host")
_lib = None
_lib_tried = False


def _lib_path() -> str:
    """The library file for the CURRENT source: the content hash is in
    the name, so freshness never rests on mtimes."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    return f"{_LIB_STEM}.{digest}.so"


def _build_library(run=subprocess.run) -> Optional[str]:
    """Compile pipeline.cpp to the shared library, safely under races.

    Two processes can reach here at once (an ElasticRunner relaunch
    racing a worker, multi-process gloo tests), and a ``dlopen`` of a
    half-written .so aborts the process — so the compiler writes to a
    private temp path and the result lands via atomic ``os.replace``,
    serialized by an exclusive file lock. A process that waited on the
    lock adopts the winner's build instead of compiling twice, and the
    builder removes the binaries of other source versions. ``run`` is
    injectable for tests."""
    lib_path = _lib_path()
    lock_path = _LIB_STEM + ".so.lock"
    tmp_path = f"{lib_path}.tmp.{os.getpid()}"
    try:
        with open(lock_path, "w") as lock_f:
            fcntl.flock(lock_f.fileno(), fcntl.LOCK_EX)
            try:
                if os.path.exists(lib_path):
                    return lib_path  # a racing builder finished first
                run(["g++", "-O3", "-shared", "-fPIC", "-o", tmp_path,
                     _SRC, "-lpthread"],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp_path, lib_path)
                for stale in glob.glob(_LIB_STEM + "*.so"):
                    if stale != lib_path:
                        with contextlib.suppress(OSError):
                            os.unlink(stale)
                return lib_path
            finally:
                fcntl.flock(lock_f.fileno(), fcntl.LOCK_UN)
    except (OSError, subprocess.SubprocessError) as e:
        detail = getattr(e, "stderr", b"") or b""
        warnings.warn(
            f"native host pipeline build failed ({e!r}"
            f"{detail[-200:].decode(errors='replace')}); using the "
            "numpy fallback", RuntimeWarning, stacklevel=2)
        return None
    finally:
        with contextlib.suppress(OSError):
            os.unlink(tmp_path)  # a failed compile's partial output


def _load_fault_injected() -> bool:
    """The 'native.load' host-chaos seam: an armed injector forces this
    load to report failure, driving the caller onto the numpy fallback
    (bitwise-identical output — the parity tests pin it). Lazy import
    keeps this module importable with ctypes+numpy alone."""
    try:
        from fedtorch_tpu.robustness import host_chaos
    except ImportError:  # partial install / standalone use
        return False
    return host_chaos.fire("native.load")


def load_library():
    """Load (building if needed) the native library; None on failure
    (or when the 'native.load' host-fault seam fires — a per-call
    forced numpy fallback that never poisons the cached handle)."""
    global _lib, _lib_tried
    if _load_fault_injected():
        return None
    if _lib is not None or _lib_tried:
        return _lib
    _lib_tried = True
    path = _lib_path()
    if not os.path.exists(path):
        path = _build_library()
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
        lib.ft_seeded_perm.argtypes = [
            ctypes.c_int64, ctypes.c_uint64,
            np.ctypeslib.ndpointer(np.int32)]
        lib.ft_gather_rows.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_int32]
        lib.ft_cyclic_pad_indices.argtypes = [
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64,
            np.ctypeslib.ndpointer(np.int32), ctypes.c_int64]
        # POINTER(c_char) rather than c_char_p so a mutable bytearray
        # (via (c_char * n).from_buffer) passes zero-copy alongside bytes
        lib.ft_svmlight_count.argtypes = [
            ctypes.POINTER(ctypes.c_char), ctypes.c_int64]
        lib.ft_svmlight_count.restype = ctypes.c_int64
        lib.ft_svmlight_scan.argtypes = [
            ctypes.POINTER(ctypes.c_char), ctypes.c_int64,
            ctypes.POINTER(ctypes.c_int64),
            ctypes.POINTER(ctypes.c_int64)]
        lib.ft_svmlight_parse.argtypes = [
            ctypes.POINTER(ctypes.c_char), ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.float32),
            np.ctypeslib.ndpointer(np.float32), ctypes.c_int32]
        lib.ft_svmlight_parse.restype = ctypes.c_int32
        _lib = lib
    except OSError:
        _lib = None
    return _lib


def native_available() -> bool:
    return load_library() is not None


def seeded_permutation(n: int, seed: int) -> np.ndarray:
    """Deterministic permutation of [0, n). Native Fisher-Yates when
    available, numpy otherwise (different but equally valid streams)."""
    lib = load_library()
    out = np.empty(n, np.int32)
    if lib is None:
        return np.random.RandomState(seed).permutation(n).astype(np.int32)
    lib.ft_seeded_perm(n, seed & 0xFFFFFFFFFFFFFFFF, out)
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray,
                num_threads: int = 0) -> np.ndarray:
    """dst[k] = src[idx[k]] over leading-axis rows, multithreaded."""
    lib = load_library()
    idx = np.ascontiguousarray(idx, np.int32)
    if lib is None:
        return np.ascontiguousarray(src[idx])
    src = np.ascontiguousarray(src)
    out = np.empty((len(idx),) + src.shape[1:], src.dtype)
    row_bytes = src.dtype.itemsize * int(np.prod(src.shape[1:], initial=1))
    lib.ft_gather_rows(src.ctypes.data, row_bytes, idx, len(idx),
                       out.ctypes.data, num_threads)
    return out


def cyclic_pad_indices(idx: np.ndarray, n_out: int) -> np.ndarray:
    lib = load_library()
    idx = np.ascontiguousarray(idx, np.int32)
    if lib is None:
        reps = -(-n_out // len(idx))
        return np.tile(idx, reps)[:n_out]
    out = np.empty(n_out, np.int32)
    lib.ft_cyclic_pad_indices(idx, len(idx), out, n_out)
    return out


def parse_svmlight(data: "bytes | bytearray",
                   n_features: Optional[int] = None,
                   num_threads: int = 0):
    """Parse svmlight/libsvm text into a dense [n, f] float32 matrix
    and float32 labels — the native multithreaded replacement for
    sklearn's parser on the real-data path (data/datasets.py
    load_libsvm). ``None`` when the native library is unavailable (the
    caller falls back to sklearn). Raises ValueError on malformed
    input (bad separator, out-of-range or non-ascending index)."""
    lib = load_library()
    if lib is None:
        return None
    if not data.endswith(b"\n"):
        if isinstance(data, bytearray):
            data += b"\n"  # in place, no copy of a multi-GB buffer
        else:
            data = data + b"\n"  # the parser's line walker requires it
    if isinstance(data, bytearray):
        # zero-copy view for the POINTER(c_char) params (bytes objects
        # pass as-is)
        cbuf = (ctypes.c_char * len(data)).from_buffer(data)
    else:
        cbuf = data
    if n_features is None:
        n_rows = ctypes.c_int64()
        max_index = ctypes.c_int64()
        lib.ft_svmlight_scan(cbuf, len(data), ctypes.byref(n_rows),
                             ctypes.byref(max_index))
        n, f = int(n_rows.value), int(max_index.value)
    else:
        # known width: the cheap line count, no scan tokenization
        n, f = int(lib.ft_svmlight_count(cbuf, len(data))), \
            int(n_features)
    labels = np.empty(n, np.float32)
    dense = np.empty((n, f), np.float32)
    rc = lib.ft_svmlight_parse(cbuf, len(data), f, labels,
                               dense.reshape(-1), num_threads)
    if rc != 0:
        raise ValueError(
            "malformed svmlight input (bad 'index:value' pair, index "
            f"out of [1, {f}], or non-ascending indices)")
    return dense, labels


class HostPrefetcher:
    """Background-thread double buffering: overlaps the host-side gather
    of the next work item with device compute (the role of the
    reference's DataLoader worker processes)."""

    def __init__(self, produce_fn, depth: int = 2,
                 name: str = "host-prefetcher"):
        self._produce = produce_fn
        self.name = name
        self._q: queue.Queue = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        # the producer's fatal exception, kept BESIDE the queued copy:
        # the queue delivers it once, but every later next() (a
        # supervisor retry, a second consumer poll) must still raise
        # the real error immediately instead of a generic 120s timeout
        self._error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._worker, daemon=True,
                                        name=name)
        self._thread.start()

    def _worker(self):
        step = 0
        while not self._stop.is_set():
            try:
                item = self._produce(step)
            except StopIteration:
                self._put(None)
                return
            except BaseException as e:  # surface producer errors
                self._error = e
                self._put(e)
                return
            if not self._put(item):
                return  # stopped while waiting for queue space
            step += 1

    def _put(self, item) -> bool:
        """Bounded-wait put that keeps observing the stop flag: a
        worker parked on a full queue must exit promptly on close()
        instead of blocking in ``Queue.put`` forever."""
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.2)
                return True
            except queue.Full:
                continue
        return False

    def next(self, timeout: float = 60.0):
        """Next produced item, liveness-aware: a DEAD producer raises
        its stored exception (or a named death report) at the next
        short poll instead of burning the full ``timeout`` on an empty
        queue, and a timeout with the thread still ALIVE raises a
        :class:`TimeoutError` naming the wedged thread — the name to
        look for in the watchdog's stack dump."""
        deadline = time.monotonic() + timeout
        while True:
            try:
                item = self._q.get(timeout=min(
                    0.2, max(deadline - time.monotonic(), 0.01)))
            except queue.Empty:
                # GIL-atomic single store: the worker writes _error
                # exactly once (then exits) and this side only reads —
                # a lock would add a queue-poll-rate hot path for a
                # once-per-lifetime publication
                if self._error is not None:  # lint: disable=FTH003 — worker's one write precedes its exit; reference-assignment is atomic
                    raise RuntimeError(
                        f"{self.name!r} producer thread died: "
                        f"{self._error!r}") from self._error
                if not self._thread.is_alive():
                    raise RuntimeError(
                        f"{self.name!r} producer thread exited without "
                        "delivering an item or an error")
                if time.monotonic() >= deadline:
                    raise TimeoutError(
                        f"{self.name!r} produced nothing for "
                        f"{timeout:.0f}s with its thread still alive — "
                        f"a WEDGED producer; look for thread "
                        f"{self.name!r} in the watchdog's stack dump")
                continue
            if isinstance(item, BaseException):
                raise item
            return item

    def alive(self) -> bool:
        """Producer-thread liveness (False once it exited — normally,
        after an error, or via close)."""
        return self._thread.is_alive()

    def depth(self) -> int:
        """Items currently buffered (approximate by nature — the worker
        appends concurrently); the stream plane's prefetch-depth gauge
        (fedtorch_tpu.telemetry): depth 0 at fetch time means the
        consumer is about to block on the producer."""
        return self._q.qsize()

    def close(self, join_timeout: float = 5.0) -> bool:
        """Stop the producer and drop queued items. Returns True when
        the worker thread actually exited within the bounded join —
        False means it is still finishing one in-flight produce call
        (it observes the stop flag at its next put and exits on its
        own; the thread is a daemon, so a drain with a deadline is
        never blocked on it). Idempotent."""
        self._stop.set()
        try:
            while True:
                self._q.get_nowait()
        except queue.Empty:
            pass
        self._thread.join(timeout=join_timeout)
        return not self._thread.is_alive()
