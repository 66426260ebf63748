"""Executor host loop on the mesh: median milliseconds per push of the
program's ``stream.place`` span (the chunk put one shard row per device,
inside ``stream.dispatch``), from the trace. ``None`` on a program without
the span, and off the mesh, where it never opens."""
import statistics

import _spans

#: The program's span (``repro.obs.spans.PLACE``).
PLACE = "stream.place"


def read(ctx):
    if ctx.trace is None:
        return None
    roots = _spans.nest(ctx.trace.spans, _spans.NAMES + (PLACE,),
                        ctx.trace.t0)
    ms = [sp.ms for push in roots if push.name == _spans.PUSH
          for sp in push.named(PLACE)]
    return statistics.median(ms) if ms else None
