"""Dataplane: device milliseconds per traced call of the entry's executable,
the union of its op intervals, averaged over the cell's chips."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.calls:
        return None
    return 1e3 * t.mean_over_devices(t.module_busy_s) / t.calls
