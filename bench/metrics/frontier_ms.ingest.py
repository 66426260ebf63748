"""Executor host loop: median milliseconds of the program's
``stream.frontier`` span (the host frontier mirror and the close test)
inside the pushes that closed no interval, from the trace."""
import _spans


def read(ctx):
    return _spans.ingest_median_ms(ctx.trace, _spans.FRONTIER)
