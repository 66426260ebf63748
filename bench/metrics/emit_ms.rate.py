"""Emission, open loop: host milliseconds per emission, from pushes that
fired emissions less the median push that fired none."""
from _common import emit_ms


def read(ctx):
    return emit_ms(ctx.window)
