"""Device busy time per tick, in ms: the union of the intervals in which an
op ran on the device in the traced window, averaged over chips, divided
by the ticks in the window."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.busy_ns <= 0:
        return None
    return t.busy_ns / t.ticks * 1e-6
