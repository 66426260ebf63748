"""Ingest step: device milliseconds of the jitted ingest step (``core``:
watermark routing, ring maintenance, the fold) per chunk, from the trace."""
from _common import STEP_MODULE, per_run_ms


def read(ctx):
    return per_run_ms(ctx.trace, STEP_MODULE, label="ingest step")
