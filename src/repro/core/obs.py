"""Spans of the control plane's tick, on the profiler's own clock.

``span(name, tick=, bytes=, program=)`` marks one stage of a tick
(``ppa.collect``, ``ppa.forecast.readback``, ...; the tree is in
docs/architecture.md, "Observability").  Off, the default, it returns one
shared no-op context manager: no stats are gathered or formatted.  On, it
returns a ``jax.profiler.TraceAnnotation``: a TraceMe event in the
profiler's host plane, on the same clock as the device ops, with the
stats that were given.  The profiler keeps the events while a trace runs
and writes them when it stops; this module records nothing of its own.

    obs.enable(True)
    jax.profiler.start_trace(log_dir)
    ...                                   # ticks
    jax.profiler.stop_trace()
    obs.enable(False)
"""
from __future__ import annotations

import contextlib

from jax.profiler import TraceAnnotation

_NULL = contextlib.nullcontext()
_on = False


def enable(on: bool = True) -> None:
    """Turn the plane's spans on or off (process-wide)."""
    global _on
    _on = bool(on)


def enabled() -> bool:
    return _on


def span(name: str, tick: int | None = None, bytes: int | None = None,
         program: str | None = None):
    """A context manager around one stage: a profiler span when spans are
    on, the shared no-op otherwise.  The stats are named parameters, so a
    call site builds no dict while spans are off."""
    if not _on:
        return _NULL
    meta = {k: v for k, v in (("tick", tick), ("bytes", bytes),
                              ("program", program)) if v is not None}
    return TraceAnnotation(name, **meta)
