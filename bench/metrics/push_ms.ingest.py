"""Executor host loop: median host milliseconds of ``PipelinedExecutor.push``
over the pushes that closed no interval (benchmark spans around ``push``)."""
import statistics

from _common import ingest_push_ms


def read(ctx):
    ms = ingest_push_ms(ctx.window)
    return statistics.median(ms) if ms else None
