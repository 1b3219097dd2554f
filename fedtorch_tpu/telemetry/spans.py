"""Host-span tracing exported as Chrome trace-event JSON.

The profiler (``jax.profiler``) attributes time *inside* one XLA
program; what it cannot see is the host side of a round — schedule
replay, feed gather, H2D dispatch, the dispatch gap between rounds,
scalar fetch, eval, checkpoint IO. Those phases are where most of the
loop's wall-time goes (PERF.md section 5), and :class:`SpanRecorder`
makes them visible facts: every instrumented host phase becomes a
complete event (``ph: "X"``) in a ``trace.json`` loadable in Perfetto /
chrome://tracing, with thread lanes for the CLI loop, the stream-feed
producer, and the async checkpoint writer.

Overhead discipline: opening+closing a span is two
``time.perf_counter_ns`` calls and one ``list.append`` (GIL-atomic, so
producer/writer threads record without locks). The buffer is
bounded (``max_events``); past the cap new spans are counted as
dropped instead of growing without bound on month-long runs.

A span that asks (``span(...).rss()``: the loop's ``eval`` and
``checkpoint``, twice a cycle) also carries the process's resident set
at enter and at exit, read outside its own clock reads; a span that
does not ask runs the code it always ran.

With an ``annotate`` hook (the CLI passes
``jax.profiler.TraceAnnotation``; this package imports no jax) every
span also opens an annotation of the same name and args, so that while
a profile is being taken the host spans lie in the profiler's own file
beside the device's operations, on one clock. With no profile running
the annotation is a flag test.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import Callable, Dict, List, Optional

_PROC_STATUS = "/proc/self/status"


def _vm_rss(arg: str) -> Dict[str, int]:
    """``{arg: bytes}``: the ``VmRSS`` line of ``/proc/self/status``
    (kB there); empty where there is no such file or no such line."""
    try:
        with open(_PROC_STATUS, "rb") as f:
            for line in f:
                if line.startswith(b"VmRSS:"):
                    return {arg: int(line.split()[1]) * 1024}
    except OSError:
        pass
    return {}


class _Span:
    """Reusable context manager for one span (allocation-light: one
    object per ``span()`` call, no closure)."""

    __slots__ = ("_rec", "name", "args", "_t0", "_ann")

    def __init__(self, rec: "SpanRecorder", name: str, args: Optional[Dict]):
        self._rec = rec
        self.name = name
        self.args = args
        self._ann = None

    def __enter__(self) -> "_Span":
        annotate = self._rec.annotate
        if annotate is not None:
            self._ann = annotate(self.name, **(self.args or {}))
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def note(self, **args) -> None:
        """Args that are known only inside the span (an outcome),
        recorded with it at exit. The profiler's annotation keeps the
        args it was opened with."""
        self.args = {**(self.args or {}), **args}

    def rss(self) -> "_Span":
        """The same span, asked for the process's resident set: args
        ``vm_rss_enter`` and ``vm_rss_exit`` in bytes, recorded at exit
        as :meth:`note` args are (their difference is what the span's
        work left resident: buffers found again read 0, fresh ones
        their size). Absent off Linux. Call it before entering."""
        return _RssSpan(self._rec, self.name, self.args)

    def __exit__(self, *exc) -> None:
        self._rec._record(self.name, self._t0, time.perf_counter_ns(),
                          self.args)
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _RssSpan(_Span):
    """What :meth:`_Span.rss` hands back. Both reads of the file lie
    outside the span's two clock reads, so its duration is that of the
    work alone."""

    __slots__ = ()

    def __enter__(self) -> "_RssSpan":
        self.note(**_vm_rss("vm_rss_enter"))
        return super().__enter__()

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._rec._record(self.name, self._t0, t1,
                          {**self.args, **_vm_rss("vm_rss_exit")})
        if self._ann is not None:
            self._ann.__exit__(*exc)


class _NullSpan:
    """The disabled path: one shared instance, empty enter/exit."""

    __slots__ = ()

    def __enter__(self):
        return self

    def note(self, **args) -> None:
        return None

    def rss(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc):
        return None


NULL_SPAN = _NullSpan()


class SpanRecorder:
    """In-memory span buffer with a Chrome trace-event exporter.

    ``ts``/``dur`` are microseconds relative to the recorder's creation
    (the Chrome format treats the origin as arbitrary); the absolute
    wall-clock origin is recorded as trace metadata so spans can be
    correlated with profiler captures and log timestamps.
    """

    def __init__(self, max_events: int = 200_000,
                 pid: Optional[int] = None,
                 annotate: Optional[Callable] = None):
        self.pid = pid if pid is not None else os.getpid()
        self.max_events = int(max_events)
        # ``annotate(name, **args)`` -> context manager opened with
        # every span (module docstring); None records spans alone
        self.annotate = annotate
        self.origin_ns = time.perf_counter_ns()
        self.origin_unix = time.time()
        self.dropped = 0
        self._events: List[tuple] = []  # (name, t0, t1, tid, args)
        self._instants: List[tuple] = []  # (name, t, tid, args)
        # tid -> thread name, captured at RECORD time: worker threads
        # (the stream producer, the checkpoint writer) exit before the
        # run-end export, when threading.enumerate() can no longer
        # name them — their lanes must not degrade to "thread-<id>"
        self._names: Dict[int, str] = {}

    # -- recording ------------------------------------------------------
    def span(self, name: str, /, **args) -> _Span:
        return _Span(self, name, args or None)

    def _record(self, name, t0, t1, args) -> None:
        if len(self._events) >= self.max_events:
            self.dropped += 1
            return
        tid = threading.get_ident()
        if tid not in self._names:
            self._names[tid] = threading.current_thread().name
        self._events.append((name, t0, t1, tid, args))

    def span_at(self, name: str, start_unix: float, end_unix: float,
                **args) -> None:
        """A span that was timed elsewhere, on the wall clock (JAX's
        compile reports, ``utils.tracing.CompileSpans``): moved onto
        the recorder's clock through the origin both clocks share."""
        t0 = self.origin_ns + int((start_unix - self.origin_unix) * 1e9)
        t1 = self.origin_ns + int((end_unix - self.origin_unix) * 1e9)
        self._record(name, t0, max(t1, t0), args or None)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (``ph: "i"``) — used for correlating
        external windows (profiler captures) and one-shot events."""
        if len(self._instants) >= self.max_events:
            self.dropped += 1
            return
        tid = threading.get_ident()
        if tid not in self._names:
            self._names[tid] = threading.current_thread().name
        self._instants.append((name, time.perf_counter_ns(), tid,
                               args or None))

    def __len__(self) -> int:
        return len(self._events) + len(self._instants)

    # -- export ---------------------------------------------------------
    def _us(self, t_ns: int) -> float:
        return (t_ns - self.origin_ns) / 1e3

    def to_trace_events(self) -> List[Dict]:
        """The Chrome trace-event list (JSON-ready dicts)."""
        # thread-name metadata: Perfetto renders these as lane labels
        # (record-time capture in self._names; live threads refresh it
        # in case one was renamed)
        names = dict(self._names)
        names.update({t.ident: t.name for t in threading.enumerate()})
        tids = {tid for *_, tid, _ in self._events} \
            | {tid for _, _, tid, _ in self._instants}
        out: List[Dict] = [
            {"name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
             "args": {"name": "fedtorch_tpu host"}},
        ]
        for tid in sorted(tids):
            out.append({"name": "thread_name", "ph": "M", "pid": self.pid,
                        "tid": tid,
                        "args": {"name": names.get(tid, f"thread-{tid}")}})
        for name, t0, t1, tid, args in self._events:
            ev = {"name": name, "cat": "host", "ph": "X",
                  "ts": self._us(t0), "dur": (t1 - t0) / 1e3,
                  "pid": self.pid, "tid": tid}
            if args:
                ev["args"] = args
            out.append(ev)
        for name, t, tid, args in self._instants:
            ev = {"name": name, "cat": "host", "ph": "i", "s": "p",
                  "ts": self._us(t), "pid": self.pid, "tid": tid}
            if args:
                ev["args"] = args
            out.append(ev)
        return out

    def export(self, path: str) -> int:
        """Write the Perfetto-loadable trace file; returns the event
        count. Atomic (tmp + rename) so a crash mid-export never leaves
        a torn file where a monitor expects JSON."""
        doc = {
            "traceEvents": self.to_trace_events(),
            "displayTimeUnit": "ms",
            "otherData": {
                "origin_unix": self.origin_unix,
                "dropped_spans": self.dropped,
            },
        }
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return len(self)
