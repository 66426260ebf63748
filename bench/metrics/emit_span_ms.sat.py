"""Emission, closed loop: mean milliseconds of the program's ``stream.emit``
span per close (argument conversion, the emission's dispatch, the wait on
its results and the record), from the trace."""
import _spans


def read(ctx):
    return _spans.per_close_ms(ctx.trace, _spans.EMIT)
