"""The plain reference of what the timed path computes, independent of the
program: it imports nothing from ``src/`` and is given only the seeded
inputs (metric rows, weights), never anything the program made.

* Forecast: standardise the window with per-target scaler statistics
  fitted on the history, run the architecture's forward
  (``models/<arch>.py``), add the last standardised row back (residual
  forecasters), invert the scaling.  Copied from the semantics of
  ``Scaler`` and ``LSTMForecaster.predict`` in
  ``src/repro/core/forecaster.py``.
* Decision: the scalar ``ThresholdPolicy.__call__``
  (``src/repro/core/policies.py``), the ``Evaluator`` clamp to the maximum
  (``src/repro/core/evaluator.py``) and the Kubernetes scale-down
  stabiliser (``ScaleDownStabilizer.apply``, ``src/repro/core/ppa.py``),
  written out over a (Z,) batch of targets.
"""
from __future__ import annotations

import numpy as np

Z_CLIP = 10.0          # z-score clamp of every transform path


def identity(x):
    return x


def scaler_stats(history: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Per-target ``(mean, std)`` (Z, M) from history rows (T, Z, M), with
    the relative floor that keeps a constant column finite."""
    mean = history.mean(axis=0)
    std = np.maximum(history.std(axis=0), 0.01 * (np.abs(mean) + 1.0))
    return mean, std


def forecast(arch, leaves: dict, mean, std, win, residual: bool,
             rnd=identity) -> np.ndarray:
    """Forecasts (Z, T, M) in metric units from the windows ``win``
    (Z, T, W, M) of T ticks; ``mean`` and ``std`` (Z, M) hold for every
    tick.  ``rnd`` rounds every intermediate (the lower-precision
    control)."""
    mean, std = mean[:, None, :], std[:, None, :]
    z = rnd(np.clip((win - mean[:, :, None]) / std[:, :, None],
                    -Z_CLIP, Z_CLIP))
    net = arch.forward({k: rnd(v) for k, v in leaves.items()}, z, rnd)
    if residual:
        net = rnd(z[:, :, -1, :] + net)
    return net * std + mean


def threshold_policy(key, cur, threshold, min_replicas, tolerance):
    """``ceil(key / threshold)`` with the HPA tolerance dead band around
    the current count, at least ``min_replicas``; a non-finite key holds
    the current count."""
    with np.errstate(divide="ignore", invalid="ignore"):
        dead = (cur > 0) & (np.abs(key / (threshold * cur) - 1.0)
                            <= tolerance)
    n = np.maximum(np.ceil(np.maximum(key, 0.0) / threshold), min_replicas)
    n = np.where(dead | ~np.isfinite(key), np.maximum(cur, min_replicas), n)
    return np.minimum(n, 2.0**62).astype(np.int64)


class Decider:
    """Decisions of Z targets tick by tick: policy on the key metric (the
    forecast where there is one, else the current value), clamp to the
    maximum, then the scale-down stabiliser over ``window_s``.

    The stabiliser's largest recommendation in the window is a running
    maximum over two stacks of records, so that a tick takes a few
    elementwise maxima and not one over every record in the window: the
    older stack holds each record with the maximum of it and every later
    record there, the newer one only the maximum of all of its records.
    When the oldest record leaves the window and the older stack is empty,
    the newer stack becomes the older one."""

    def __init__(self, threshold, min_replicas, tolerance, window_s):
        self.threshold = threshold
        self.min_replicas = min_replicas
        self.tolerance = tolerance
        self.window_s = window_s
        self.older: list[tuple[float, np.ndarray]] = []
        self.newer: list[tuple[float, np.ndarray]] = []
        self.newer_max = None

    def __call__(self, t, current_key, forecast_key, cur, max_r):
        key = np.where(np.isfinite(forecast_key), forecast_key, current_key)
        n = threshold_policy(key, cur, self.threshold, self.min_replicas,
                             self.tolerance)
        n = np.minimum(n, max_r)
        self.newer.append((t, n))
        self.newer_max = n if self.newer_max is None \
            else np.maximum(self.newer_max, n)
        lo = t - self.window_s
        while True:
            while self.older and self.older[0][0] < lo:
                self.older.pop(0)
            if self.older or self.newer[0][0] >= lo:
                break
            acc = None
            for tt, d in reversed(self.newer):
                acc = d if acc is None else np.maximum(acc, d)
                self.older.append((tt, acc))
            self.older.reverse()
            self.newer, self.newer_max = [], None
        if not self.older:
            recmax = self.newer_max
        elif self.newer_max is None:
            recmax = self.older[0][1]
        else:
            recmax = np.maximum(self.older[0][1], self.newer_max)
        return np.where(n < cur, np.minimum(recmax, max_r), n)
