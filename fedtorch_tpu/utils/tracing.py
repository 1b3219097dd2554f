"""Profiling / tracing — and the recompilation sentinel.

The reference only hand-times phases (SURVEY.md §5.1); the TPU build adds
real profiler traces: ``jax.profiler`` emits a TensorBoard-compatible
trace of the XLA execution (HLO ops, fusion, collective time on ICI),
which is the per-phase attribution the hand timers cannot see inside one
compiled round.

The **recompilation sentinel** is the runtime half of the tracing-hazard
gate (static half: ``fedtorch_tpu.lint``, docs/static_analysis.md).
Hot callables are registered with :func:`instrument_trace` before they
are handed to ``jax.jit``; tracing executes the wrapped Python body, so
each body execution == one trace event, while steady-state compiled
calls never re-enter Python.  :class:`RecompilationSentinel` scopes the
counting: the tier-1 test asserts the FedAvg/SCAFFOLD round programs
trace exactly once across many rounds and fault schedules — the
"static config => unchanged traced program" contract PR 1's chaos
machinery depends on.
"""
from __future__ import annotations

import functools
import time
from collections import Counter
from typing import Callable, Dict, List, Optional

import jax

# process-lifetime trace counts per instrumented callable name; the
# sentinel snapshots deltas of this via its own scoped counter
_TRACE_COUNTS: Counter = Counter()
_ACTIVE_SENTINELS: List["RecompilationSentinel"] = []


def instrument_trace(name: str, fn: Optional[Callable] = None):
    """Wrap ``fn`` so each execution of its PYTHON body is counted as a
    trace event under ``name``.  Apply to the function handed to
    ``jax.jit`` (inside the jit boundary the body only runs while
    tracing); also usable as ``@instrument_trace("name")``.

    Counts are trace events, not compiles: with the persistent
    compilation cache warm, a retrace still re-executes the body (and
    still costs trace+lowering time) even though XLA compilation is
    skipped — which is exactly what the sentinel must see.
    """
    def deco(f: Callable) -> Callable:
        @functools.wraps(f)
        def wrapped(*args, **kwargs):
            record_trace_event(name)
            return f(*args, **kwargs)
        wrapped.__fedtorch_trace_name__ = name
        return wrapped
    return deco if fn is None else deco(fn)


def record_trace_event(name: str) -> None:
    _TRACE_COUNTS[name] += 1
    for s in _ACTIVE_SENTINELS:
        s.counts[name] += 1


def trace_counts() -> Dict[str, int]:
    """Process-lifetime trace counts (name -> events)."""
    return dict(_TRACE_COUNTS)


class RecompilationSentinel:
    """Scoped trace-event counter.

    ::

        with RecompilationSentinel() as s:
            for _ in range(rounds):
                server, clients, m = trainer.run_round(server, clients)
        s.assert_traces("federated.round[fedavg]", expected=1)

    Any count above ``expected`` means something retraced the round
    program mid-run — a shape/dtype/static-arg change the static
    analyzer (fedtorch_tpu.lint) exists to catch before it ships.
    """

    def __init__(self):
        self.counts: Counter = Counter()

    def __enter__(self) -> "RecompilationSentinel":
        self.counts = Counter()
        _ACTIVE_SENTINELS.append(self)
        return self

    def __exit__(self, *exc) -> None:
        _ACTIVE_SENTINELS.remove(self)

    def count(self, name: str) -> int:
        return self.counts[name]

    def assert_traces(self, name: str, expected: int = 1) -> None:
        got = self.counts[name]
        if got != expected:
            raise AssertionError(
                f"'{name}' traced {got}x, expected {expected}x — "
                f"a retrace crept into the hot path. All counts: "
                f"{dict(self.counts) or '{}'}")


# JAX's own compile reports (``jax.monitoring``; emitted by
# ``jax/_src/dispatch.py:log_elapsed_time`` and ``compiler.py``) and the
# span each becomes
_COMPILE_SPAN_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "jax.trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "jax.lower",
    "/jax/core/compile/backend_compile_duration": "jax.compile",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


class CompileSpans:
    """Every trace, lowering and backend compile of the process as a
    span of the run's recorder, from JAX's own reports: ``jax.trace``,
    ``jax.lower`` and ``jax.compile`` (args ``fun``, at the start and
    end JAX gives) and ``jax.cache_load`` (a persistent-cache read, so
    a hit; JAX reports its duration, so the span ends where it is
    reported).

    Where :class:`RecompilationSentinel` counts Python-body traces of
    the callables registered with :func:`instrument_trace`, this sees
    every program, eager operations included. A ``jax.compile`` span
    covers the cache lookup, so on a hit ``jax.cache_load`` lies
    inside it. The CLI installs one with the telemetry and closes it
    with it; ``recorder`` is a ``telemetry.SpanRecorder``."""

    def __init__(self, recorder):
        self._rec = recorder
        self._installed = False

    def install(self) -> "CompileSpans":
        jax.monitoring.register_event_time_span_listener(self._on_span)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        self._installed = True
        return self

    def close(self) -> None:
        """Take the listeners out again. Idempotent."""
        if not self._installed:
            return
        self._installed = False
        jax.monitoring.unregister_event_time_span_listener(self._on_span)
        jax.monitoring.unregister_event_duration_listener(
            self._on_duration)

    def _on_span(self, event, start, end, **kw) -> None:
        name = _COMPILE_SPAN_OF.get(event)
        if name is not None:
            self._rec.span_at(name, start, end,
                              fun=str(kw.get("fun_name", "")))

    def _on_duration(self, event, duration, **kw) -> None:
        if event == _CACHE_LOAD_EVENT:
            end = time.time()
            self._rec.span_at("jax.cache_load", end - duration, end)


def fetch_sync(out):
    """Force real completion of ``out`` (any pytree) via a 1-element
    device->host fetch of its first leaf; returns the fetched value.

    THE canonical drain for timing/tracing boundaries: materializing
    result bytes on the host waits for the in-order device stream on
    any backend. (On the TPU v5e runtime ``jax.block_until_ready``
    waits as well — measured against a matmul chain's FLOPs floor,
    scripts/bench_timing.py.) ``scripts/bench_timing.py`` re-exports
    this for ``scripts/compare_reference.py``."""
    import numpy as np

    leaf = jax.tree_util.tree_leaves(out)[0]
    # lint: disable=FTL001 — this 1-element fetch IS the sync
    return np.asarray(leaf[(0,) * getattr(leaf, "ndim", 0)])


def live_buffer_summary() -> dict:
    """Live ``jax.Array`` accounting: total ADDRESSABLE bytes (each
    replicated copy counted — the buffers a device actually holds) and
    a per-(shape, dtype) breakdown.

    A device's ``memory_stats()`` is allocator-dependent and returns
    nothing on the CPU backend, so the streaming-residency contract
    ("the device holds the double-buffered feed, not the client store",
    tests/test_streaming.py) is asserted against THIS view, which works
    on every platform: what the program still holds references to,
    shape by shape."""
    by_shape: Dict[str, int] = {}
    total = 0
    for a in jax.live_arrays():
        try:
            n = sum(int(s.data.nbytes) for s in a.addressable_shards)
        except Exception:
            try:
                n = int(a.size) * a.dtype.itemsize
            except Exception:
                continue
        key = f"{tuple(a.shape)}:{a.dtype}"
        by_shape[key] = by_shape.get(key, 0) + n
        total += n
    return {"total_bytes": total, "by_shape": by_shape}
