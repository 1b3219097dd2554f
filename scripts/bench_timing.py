"""Fetch-synced device timing for ``scripts/compare_reference.py``.

A timed call syncs by fetching one element of its final output:
materializing result BYTES on the host waits for the in-order device
stream on any backend. On the TPU v5e runtime
``jax.block_until_ready`` waits too — measured 2026-09-26 (PR 21, jax
0.9.0): a chain of sixteen 8192^3 bf16 matmuls blocked in 95.1 ms
against a 197 TFLOP/s floor of 89.3 ms, and the fetch added 2 ms — so
the two syncs are interchangeable there; the fetch stays because it
holds on any backend.

The implementation lives in ``fedtorch_tpu.utils.tracing.fetch_sync``;
this module is the import surface of ``scripts/compare_reference.py``.
"""
from __future__ import annotations

import time

from fedtorch_tpu.utils.tracing import fetch_sync as sync  # noqa: F401


def timeit(fn, *args, iters: int = 20, sync_each: bool = False) -> float:
    """Mean seconds per call over `iters` dispatches, fetch-synced.

    Default mode queues all `iters` dispatches and drains ONCE at the
    end — the steady-state number (per-call dispatch overhead hides
    behind device compute), resting on the assumption that the device
    executes the queued calls in order and the final fetch therefore
    waits for all of them.

    ``sync_each=True`` is the opt-in cross-check mode: every iteration
    drains through a fetch before the next dispatch.
    It reads strictly slower (per-call transfer latency lands on the
    clock), but it cannot be fooled by a backend that reorders,
    coalesces, or drops queued work — see :func:`timeit_crosscheck`.
    """
    sync(fn(*args))  # warmup/compile, fully drained
    if sync_each:
        t0 = time.perf_counter()
        for _ in range(iters):
            sync(fn(*args))
        return (time.perf_counter() - t0) / iters
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    sync(out)
    return (time.perf_counter() - t0) / iters


def timeit_crosscheck(fn, *args, iters: int = 20,
                      suspect_ratio: float = 3.0) -> dict:
    """Validate a queued-mode reading against the per-iteration-sync
    mode (the queued-in-order assumption check).

    Physics bounds the honest relationship: ``synced`` >= ``queued``
    (it adds a round-trip per call) but by roughly the fetch latency,
    not by orders of magnitude. ``synced / queued > suspect_ratio``
    flags a SUSPICIOUS queued reading — the signature of a backend
    that acknowledged dispatches without executing them, where queued
    mode times dispatch and only the cross-check pays for real
    execution. Callers
    seeing ``suspicious=True`` should report ``synced_s`` (an upper
    bound) and distrust the artifact's queued numbers."""
    queued = timeit(fn, *args, iters=iters)
    synced = timeit(fn, *args, iters=iters, sync_each=True)
    ratio = synced / queued if queued > 0 else float("inf")
    return {
        "queued_s": queued,
        "synced_s": synced,
        "sync_overhead_ratio": ratio,
        "suspect_ratio": suspect_ratio,
        "suspicious": ratio > suspect_ratio,
    }
