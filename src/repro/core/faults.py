"""Forecast and refit failures the control plane survives, and the ones it
must not (DESIGN.md §13).

The plane serves a tick reactively when a forecast dispatch fails while it
runs, and drops a background refit whose compute raises.  Both stay, and
both are counted in ``FaultLog`` so that ``degraded_stats()`` shows them.

A program that cannot be traced, lowered or compiled is a fault in the
program, not in the run: it would fail the same way on every tick.
``Staged`` builds a jitted program as a step of its own before running it,
and raises ``ProgramFault`` when that step fails; the plane lets
``ProgramFault`` propagate instead of serving the tick reactively.
"""
from __future__ import annotations

import threading
import time

import jax
import numpy as np

from repro.core import obs


class ProgramFault(RuntimeError):
    """A forecast program failed to trace, lower or compile."""


class Staged:
    """A jitted function whose build (trace, lower, compile) runs before
    its first execution for each argument signature.  A failed build
    raises ``ProgramFault`` chained to the cause; a failure while the
    compiled program runs raises as it is.  Arguments must be concrete
    arrays: the callers run it outside any trace.  ``builds`` and
    ``build_s`` count the builds that succeeded and their seconds; each
    build is a ``ppa.build`` span."""

    def __init__(self, jitted):
        self._jitted = jitted
        self.name = getattr(jitted, "__name__", str(jitted))
        self._exe: dict = {}
        self.builds = 0
        self.build_s = 0.0

    def __call__(self, *args, **static):
        leaves, tree = jax.tree.flatten(args)
        key = (tree, tuple(sorted(static.items())),
               tuple((np.shape(x), np.result_type(x),
                      getattr(x, "weak_type", False),
                      getattr(x, "sharding", None)) for x in leaves))
        exe = self._exe.get(key)
        if exe is None:
            t0 = time.perf_counter()
            with obs.span("ppa.build", program=self.name):
                try:
                    exe = self._jitted.lower(*args, **static).compile()
                except Exception as e:
                    raise ProgramFault(
                        f"{type(e).__name__} while building {self.name}: "
                        f"{e}") from e
            self.builds += 1
            self.build_s += time.perf_counter() - t0
            self._exe[key] = exe
        return exe(*args)

    def executables(self) -> list:
        """The compiled programs built so far (``as_text()`` shows what
        the compiler put in them)."""
        return list(self._exe.values())


class FaultLog:
    """Counts of the failures a plane survived, shared by its shards and
    its device engine (forecasts may fail on worker threads, hence the
    lock).  ``last_error`` keeps the text of the latest one."""

    def __init__(self):
        self.forecast_errors = 0
        self.refit_failures = 0
        self.last_error: str | None = None
        self._lock = threading.Lock()

    def forecast_failed(self, exc: Exception):
        """Record a forecast dispatch that failed while it ran; re-raise a
        ``ProgramFault``, which no reactive tick may hide."""
        self._record(exc, "forecast_errors")

    def refit_failed(self, exc: Exception):
        """Record a dropped refit; re-raise a ``ProgramFault``."""
        self._record(exc, "refit_failures")

    def _record(self, exc: Exception, counter: str):
        if isinstance(exc, ProgramFault):
            raise exc
        with self._lock:
            setattr(self, counter, getattr(self, counter) + 1)
            self.last_error = f"{type(exc).__name__}: {exc}"
