"""Device, open loop: share of the traced window in which no operation
ran on the chip (1 minus the union of the ops' intervals over the window),
averaged over the chips used."""


def read(ctx):
    tr = ctx.trace
    return tr.idle_pct() if tr is not None and tr.devices else None
