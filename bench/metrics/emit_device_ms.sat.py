"""Emission: device milliseconds of the jitted emission program per
emission, from the trace."""
from _common import EMIT_MODULE, per_run_ms


def read(ctx):
    return per_run_ms(ctx.trace, EMIT_MODULE, label="emission")
