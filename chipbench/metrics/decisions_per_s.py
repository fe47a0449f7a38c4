"""Decisions per second: targets x ticks completed over the window's
seconds -- all the work over all the time of the window."""


def read(ctx):
    return ctx.Z * len(ctx.tick_s) / ctx.window_s
