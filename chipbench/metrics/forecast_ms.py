"""Mean time per tick of the benchmark's host span ``forecast`` in the traced
window, in ms (see tracing.py for what each span wraps)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.span_ns.get("forecast"):
        return None
    return sum(t.span_ns["forecast"]) / len(t.span_ns["forecast"]) * 1e-6
