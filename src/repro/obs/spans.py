"""Host spans on the profiler trace, inside the executor's push and
emission.

Each span is a ``jax.profiler.TraceAnnotation``: a TraceMe on the host
plane of the same trace as the device's programs, so its time lines up
with theirs.  Nothing is recorded unless a profiler trace is running
(``jax.profiler.trace(dir)`` or ``start_trace``/``stop_trace``); that is
the only switch.  The spans wrap host code only: the compiled programs,
their jaxprs and trace counts are the same with or without them.

The names are fixed strings, so opening a span formats nothing:

* ``stream.push``     — one ``PipelinedExecutor.push``, any close it
  triggers included;
* ``stream.dispatch`` — dispatch of the ingest step, the chunk's transfer
  (and its placement on the mesh) included;
* ``stream.place``    — on the mesh only, the chunk's placement one shard
  row per device (``records.place_sharded``), inside ``stream.dispatch``
  in the pipelined executor and at the batched executor's flush;
* ``stream.frontier`` — the host frontier mirror and the close test;
* ``stream.emit``     — one closed interval's emission: argument
  conversion, dispatch, the wait on its results and the record;
* ``stream.readback`` — the blocking reads of a record: watermark totals
  and capacity.
"""
from __future__ import annotations

import jax

PUSH = "stream.push"
DISPATCH = "stream.dispatch"
FRONTIER = "stream.frontier"
EMIT = "stream.emit"
READBACK = "stream.readback"
PLACE = "stream.place"

NAMES = (PUSH, DISPATCH, FRONTIER, EMIT, READBACK, PLACE)


def span(name: str) -> jax.profiler.TraceAnnotation:
    """A host span named ``name`` (one of :data:`NAMES`), as a context
    manager."""
    return jax.profiler.TraceAnnotation(name)
