"""Unified run telemetry (docs/observability.md).

Three pillars, one subsystem:

* **Structured metrics/events** — schema-versioned ``metrics.jsonl`` /
  ``events.jsonl`` per run dir, populated exclusively from host-side
  values (the round loop's ONE batched scalar fetch plus host
  counters): zero added device syncs, FTL001-clean by construction.
* **Host-span tracing** — ``telemetry.span("h2d", round=r)`` records
  host phases into a Chrome trace-event ``trace.json`` (Perfetto),
  with lanes for the CLI loop, the stream-feed producer, and the
  async checkpoint writer.
* **Machine-readable health** — the atomically-replaced per-host
  ``health.json`` (round, intent, monotonic last-progress) consumed by
  the watchdog, the restart harness, and external monitors.

The package is stdlib-only (no jax import): the ``fedtorch-tpu
report`` tool and external monitors can parse a run dir without
initializing a backend, and importing the hooks into hot modules costs
nothing.

Library-code usage (no Telemetry object in scope)::

    from fedtorch_tpu import telemetry

    with telemetry.span("stream.gather", round=r):
        ...                      # no-op unless a run installed one
    telemetry.event("supervisor.rollback", round=r, attempt=a)
"""
from __future__ import annotations

from fedtorch_tpu.telemetry.anomaly import (  # noqa: F401
    ANOMALY_FIELDS, EwmaAnomalyDetector, replay_anomalies,
)
from fedtorch_tpu.telemetry.costs import (  # noqa: F401
    PROGRAM_COSTS_SCHEMA, ProgramCostCapture, program_costs_path,
    read_program_costs, resolve_peak_tflops, validate_program_costs,
)
from fedtorch_tpu.telemetry.critical_path import (  # noqa: F401
    StreamOverlapTracker, overlap_efficiency, overlap_summary,
    round_wall_decomposition,
)
from fedtorch_tpu.telemetry.ledger import (  # noqa: F401
    LEDGER_SCHEMA, ClientLedger, ledger_path, read_client_ledger,
    suspicion_ranking, validate_client_ledger,
)
from fedtorch_tpu.telemetry.health import (  # noqa: F401
    HealthFile, health_path, read_health,
)
from fedtorch_tpu.telemetry.metrics import JsonlWriter  # noqa: F401
from fedtorch_tpu.telemetry.runtime import (  # noqa: F401
    LEVELS, Telemetry, get_active,
)
from fedtorch_tpu.telemetry.schema import (  # noqa: F401
    EVENTS_SCHEMA, HEALTH_INTENTS, HEALTH_SCHEMA, METRICS_OPTIONAL,
    METRICS_REQUIRED, METRICS_SCHEMA, count_restarts, iter_jsonl,
    load_jsonl, read_header, stitch_rows, validate_health,
    validate_metrics_row,
)
from fedtorch_tpu.telemetry.spans import (  # noqa: F401
    NULL_SPAN, SpanRecorder,
)


def span(name: str, /, **args):
    """Module-level span hook: records on the active run's recorder,
    or returns the shared no-op context when telemetry is off. The
    span's own name is positional-only, so ``name`` is free as an arg
    (``checkpoint.file_write`` carries the file's)."""
    t = get_active()
    if t is None:
        return NULL_SPAN
    return t.span(name, **args)


def event(name: str, **fields) -> None:
    t = get_active()
    if t is not None:
        t.event(name, **fields)


def instant(name: str, **args) -> None:
    t = get_active()
    if t is not None:
        t.instant(name, **args)
