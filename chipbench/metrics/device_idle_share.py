"""Device idle share: 1 minus the union of device op intervals over the
traced stretch of calls, averaged over the cell's chips, in percent."""


def read(ctx):
    t = ctx.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.mean_over_devices(t.busy_s) / t.window_s)
