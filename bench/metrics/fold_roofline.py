"""Fold kernel: share of its roofline. The least time the bytes any fold
must read (``bench/roofline.fold_bytes``: value, cell and mask of each of
the chunk's events on this chip, once) take at the chip's HBM bandwidth,
over the kernel's device time per chunk in the trace."""
import roofline

#: The compiled fold as the runtime names it today.
FOLD_OP = r"/reservoir_fold(\.\d+)?$"


def read(ctx):
    if ctx.trace is None:
        return None
    runs, secs = ctx.trace.ops(FOLD_OP, label="fold kernel")
    if not runs or secs <= 0:
        return None
    bw = roofline.peak(ctx.peaks, ctx.device_kind)["hbm_bytes_per_s"]
    least = roofline.fold_bytes(ctx.traffic["chunk"]) / bw
    return roofline.share_pct(least, secs / runs)
