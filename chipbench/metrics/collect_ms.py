"""Mean time per tick of the benchmark's host span ``collect`` in the traced
window, in ms (see tracing.py for what each span wraps)."""


def read(ctx):
    t = ctx.trace
    if t is None or not t.span_ns.get("collect"):
        return None
    return sum(t.span_ns["collect"]) / len(t.span_ns["collect"]) * 1e-6
