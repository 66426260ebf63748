"""Sync-free runtime observability.

Five pieces (see each module's docstring):

* :mod:`repro.obs.metrics`   — device-side cumulative counters carried as
  a :class:`~repro.runtime.executor.RuntimeState` pytree leaf (folded
  inside the already-jitted ingest — zero extra dispatches), plus the
  host-side :class:`~repro.obs.metrics.Telemetry` hub that samples them
  only at points that already synchronize (emissions, checkpoints,
  micro-batch flushes).
* :mod:`repro.obs.events`    — append-only JSONL event log with a
  versioned schema: the accuracy/staleness time series the paper's
  figures are made of, produced by the live runtime.
* :mod:`repro.obs.sentinel`  — retrace sentinel guarding the compiled
  steps: a step that retraces after warmup logs (or, opt-in, raises).
* :mod:`repro.obs.export`    — Prometheus-style text exposition + the
  event-log reductions behind ``python -m repro.obs.summarize``.
* :mod:`repro.obs.spans`     — named host spans inside the executor's
  push and emission (``jax.profiler.TraceAnnotation``), on the same
  profiler trace and clock as the device's programs; recorded only while
  a trace runs.

The invariant the whole package is built around: telemetry never adds a
host synchronization to the pipelined hot loop.  The device counters are
ALWAYS part of the ingest step (so the hot-loop jaxpr is identical with
telemetry attached or not — asserted in ``tests/test_obs.py``), and
every host-side hook fires at a boundary that already blocked.
"""
from repro.obs import events, metrics, sentinel, spans
from repro.obs.events import SCHEMA_VERSION, EventLog, read_events, validate_event
from repro.obs.metrics import MetricsState, Telemetry
from repro.obs.sentinel import RetraceError, RetraceSentinel

__all__ = [
    "events", "metrics", "sentinel", "spans",
    "SCHEMA_VERSION", "EventLog", "read_events", "validate_event",
    "MetricsState", "Telemetry", "RetraceError", "RetraceSentinel",
]
