"""The check fails a run whose timed path is broken underneath.  Each
fault is planted in the program's own path on the CPU, at a tiny size,
and the rest of the run (everything but the look for a chip) is driven as
the benchmark drives it.  The cells run on one chip, so there is no
exchange between chips to leave out."""
import sys
import time

import numpy as np
import pytest

from chipbench.harness import run_cell
from chipbench.layout import Layout


def stale_ring(monkeypatch):
    """A step that returns its state unchanged: the metric ring keeps the
    first rows it was given."""
    from repro.core.device_plane import DevicePlaneEngine
    orig = DevicePlaneEngine.push_rows

    def push_rows(self, rows):
        if not getattr(self, "_pushed", False):
            orig(self, rows)
            self._pushed = True
    monkeypatch.setattr(DevicePlaneEngine, "push_rows", push_rows)


def half_batch(monkeypatch):
    """Half of the batch left out: every other target gets no forecast."""
    from repro.core.device_plane import DevicePlaneEngine
    orig = DevicePlaneEngine.forecast

    def forecast(self, ring, counts, stale=None):
        means, cand = orig(self, ring, counts, stale)
        means, cand = means.copy(), cand.copy()
        means[::2] = np.nan
        cand[::2] = False
        return means, cand
    monkeypatch.setattr(DevicePlaneEngine, "forecast", forecast)


def altered_forecast(monkeypatch):
    """An answer altered where it is produced: one target's forecast."""
    from repro.core.device_plane import DevicePlaneEngine
    orig = DevicePlaneEngine.forecast

    def forecast(self, ring, counts, stale=None):
        means, cand = orig(self, ring, counts, stale)
        means = means.copy()
        means[-1, 1] *= 1.01
        return means, cand
    monkeypatch.setattr(DevicePlaneEngine, "forecast", forecast)


def altered_decision(monkeypatch):
    """An answer altered where it is produced: one target's decision on
    every tenth tick."""
    from repro.core.control_plane import TickResult
    orig = TickResult.replicas_array

    def replicas_array(self):
        out = orig(self)
        if int(self.t) % 150 == 0:
            out[0] += 1
        return out
    monkeypatch.setattr(TickResult, "replicas_array", replicas_array)


@pytest.mark.parametrize("fault", [stale_ring, half_batch, altered_forecast,
                                   altered_decision])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    layout = Layout(tiny_root, tiny_root / "chipbench")
    out = run_cell(layout, "lstm-tiny", 2**32 + 1, 0.3, False,
                   time.perf_counter(), require_tpu=False, log=sys.stdout)
    assert out["correct"] is False
    if fault is half_batch:
        assert out["checks"]["missing_forecasts"]["value"] > 0
        assert out["failed"] > 0
