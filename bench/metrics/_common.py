"""Shared arithmetic of the per-layer readers (not a metric itself)."""
from __future__ import annotations

import statistics

#: The runtime's compiled programs, by the name of the jitted function:
#: the pipelined ingest step and the emission (watermark or cadence).
STEP_MODULE = r"^jit_core$"
EMIT_MODULE = r"^jit_emit(_iv)?$"


def ingest_push_ms(window):
    """Host milliseconds of the pushes that closed no interval."""
    return [(e - s) * 1e3 for s, e, closes, _ in window.pushes if not closes]


def emit_ms(window):
    """Host milliseconds per emission: pushes that fired emissions, less
    the median push that fired none."""
    base = ingest_push_ms(window)
    fired = [((e - s) * 1e3, closes) for s, e, closes, _ in window.pushes
             if closes]
    if not base or not fired:
        return None
    med = statistics.median(base)
    return sum(ms - med for ms, _ in fired) / sum(n for _, n in fired)


def per_run_ms(trace, *patterns, label):
    if trace is None:
        return None
    runs, secs = trace.modules(*patterns, label=label)
    return 1e3 * secs / runs if runs else None
