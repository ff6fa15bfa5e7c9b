"""Share of the traced window in which no operation ran on the device:
1 - (union of device op intervals / window), averaged over the devices."""


def read(ctx):
    w = ctx.trace.window_s
    if w <= 0 or not ctx.trace.ops:
        return None
    return 1.0 - ctx.trace.mean_busy_s() / w
