"""Events per second: all events of the calls completed in the window,
over the window's host-clock seconds."""


def read(ctx):
    return len(ctx.window.calls) * ctx.cell.events_per_call / ctx.window.seconds
