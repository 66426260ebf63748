"""Executor host loop: median milliseconds of the program's
``stream.dispatch`` span (the ingest step's dispatch, the chunk's transfer
included) inside the pushes that closed no interval, from the trace."""
import _spans


def read(ctx):
    return _spans.ingest_median_ms(ctx.trace, _spans.DISPATCH)
