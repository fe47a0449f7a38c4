"""Mean time per tick of the benchmark's host span ``decide`` in the traced
window, in ms (see tracing.py for what each span wraps)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.span_ns.get("decide"):
        return None
    return sum(t.span_ns["decide"]) / len(t.span_ns["decide"]) * 1e-6
