"""Set-up seconds: process start to the first timed call, with imports,
traffic, static inputs, compilation and one warm-up call."""


def read(ctx):
    return ctx.setup_s
