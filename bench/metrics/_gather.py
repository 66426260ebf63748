"""Shared reading of the mesh emission's all-gather (not a metric itself).

On the TPU the emission program's all-gather is one operation
(``all-gather.N``) or an asynchronous pair (``all-gather-start.N`` ...
``all-gather-done.M``); a pair counts from its start to its done. Times
are per close: a device's gather time over its runs of the emission
program, averaged over the devices.
"""
from __future__ import annotations

import re

from _common import EMIT_MODULE

#: An all-gather of the emission program, by the qualified op name
#: ``bench/trace.py`` gives (``jit_emit_iv/all-gather-start.2``).
GATHER_OP = r"^jit_emit(_iv)?/[\w.-]*all-gather[\w.-]*$"
LABEL = "mesh collective"


def _intervals(ops):
    """``(start, end)`` of each gather among one device's ``ops``, in time
    order; an async start is paired with the next done."""
    out, open_at = [], []
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        if "-start" in name:
            open_at.append(s)
        elif "-done" in name:
            if open_at:
                out.append((open_at.pop(0), e))
        else:
            out.append((s, e))
    return out


def per_close_ms(trace):
    """Device milliseconds of the emission's all-gather per close, mean
    over the devices; ``None`` where the trace holds no such op."""
    if trace is None:
        return None
    names, per_device = set(), []
    for dev in trace.devices.values():
        ops = [(n, s, e) for n, s, e in dev["ops"]
               if s >= trace.t0 and re.search(GATHER_OP, n)]
        closes = sum(1 for n, s, _ in dev["modules"]
                     if s >= trace.t0 and re.search(EMIT_MODULE, n))
        names.update(n for n, _, _ in ops)
        spans = _intervals(ops)
        if spans and closes:
            per_device.append(
                1e3 * sum(e - s for s, e in spans) / closes)
    trace.matched[LABEL] = sorted(names)
    if not per_device:
        return None
    return sum(per_device) / len(per_device)
