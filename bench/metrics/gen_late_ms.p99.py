"""Generator: how late the open loop offered its chunks. 99th percentile
over all chunks of push start less due time, in milliseconds (benchmark
clock); nothing to read in a closed loop."""
import stats


def read(ctx):
    late = ctx.window.gen_late_ms
    return stats.percentile(late, 99) if late else None
