"""Mesh collective: share of its roofline. The least time the bytes each
chip must receive for the closed interval alone
(``bench/ici.closed_gather_bytes``) take at the chip's published
interconnect bandwidth, over the all-gather's device time per close."""
import _gather
import ici
import roofline


def read(ctx):
    ms = _gather.per_close_ms(ctx.trace)
    if not ms:
        return None
    cfg = ctx.config
    least = (ici.closed_gather_bytes(cfg["num_shards"], cfg["num_strata"],
                                     cfg["capacity_per_stratum"])
             / ici.ici_bytes_per_s(ctx.device_kind))
    return roofline.share_pct(least, ms * 1e-3)
