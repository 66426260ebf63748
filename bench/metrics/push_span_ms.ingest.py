"""Executor host loop: median milliseconds of the program's ``stream.push``
span over the pushes that closed no interval (no ``stream.emit`` inside),
from the trace."""
import _spans


def read(ctx):
    return _spans.ingest_median_ms(ctx.trace, _spans.PUSH)
