"""Share of the traced window in which no op ran on the device, in %."""


def read(ctx):
    t = ctx.trace
    if t is None or t.n_devices == 0 or t.busy_ns <= 0:
        return None
    return 100.0 * (1.0 - t.busy_ns / t.window_ns)
