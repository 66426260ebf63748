"""Mesh collective: device milliseconds per close of the emission's
all-gather (``dist.gather_cells``), async start to done, averaged over
the devices, from the trace."""
import _gather


def read(ctx):
    return _gather.per_close_ms(ctx.trace)
