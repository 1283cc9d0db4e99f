"""Host feed: host-clock milliseconds from ``device_put`` of a chunk to its
``block_until_ready``, averaged over the traced calls."""


def read(ctx):
    calls = ctx.window.traced
    return 1e3 * sum(c.phases[0] for c in calls) / len(calls) if calls else None
