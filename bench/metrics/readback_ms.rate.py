"""Emission, open loop: mean milliseconds per close of the program's
``stream.readback`` span (the record's blocking reads of the watermark
totals and the capacity), from the trace."""
import _spans


def read(ctx):
    return _spans.per_close_ms(ctx.trace, _spans.READBACK)
