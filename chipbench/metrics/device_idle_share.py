"""Device: the share of the traced window in which no operation ran on
the device (1 - union of operation intervals / window)."""


def read(ctx):
    if ctx.reduction.window_s <= 0 or ctx.reduction.devices == 0:
        return None
    return 100.0 * (1.0 - ctx.reduction.busy_s / ctx.reduction.window_s)
