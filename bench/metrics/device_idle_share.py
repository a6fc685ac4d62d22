"""Share of the traced window in which no operation ran on the device:
1 - (union of the device's op intervals) / window, averaged over the
chips used.  What the host costs: dispatch, the one sync per call and
the harness's check between calls."""


def read(ctx):
    if ctx.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.window_s)
