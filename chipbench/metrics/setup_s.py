"""Process start to the first timed tick, in seconds: generating inputs and
weights, building the plane, compiling and warming up."""


def read(ctx):
    return ctx.setup_s
