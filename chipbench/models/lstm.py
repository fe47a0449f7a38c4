"""Per-target LSTM forecaster (arXiv:2112.10127 section 5.3.1): one LSTM
layer over the window from zero state, then a ReLU-dense head.

Plain reference copied from ``src/repro/kernels/ref.py`` (``lstm_seq``),
in numpy over a leading target axis, float64 unless ``rnd`` rounds."""
from __future__ import annotations

import numpy as np

PROGRAM_CLASS = "LSTMForecaster"       # in repro.core.forecaster


def leaf_shapes(hidden: int, n_metrics: int) -> dict:
    H, M = hidden, n_metrics
    return {"Wx": (M, 4 * H), "Wh": (H, 4 * H), "b": (4 * H,),
            "Wo": (H, M), "bo": (M,)}


def sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def matvec(x, w):
    """Per-target ``x[z] @ w[z]``: (Z, K) x (Z, K, N) -> (Z, N)."""
    return (x[:, None, :] @ w)[:, 0]


def lstm(xs, Wx, Wh, b, rnd):
    """Hidden states (Z, W, H) of one LSTM layer over xs (Z, W, K), gates
    in the order i, f, g, o."""
    Z, W, _ = xs.shape
    H = Wh.shape[1]
    h = np.zeros((Z, H))
    c = np.zeros((Z, H))
    hs = []
    for t in range(W):
        gates = rnd(rnd(matvec(xs[:, t], Wx)) + rnd(matvec(h, Wh)) + b)
        i, f, g, o = np.split(gates, 4, axis=-1)
        c = rnd(rnd(sigmoid(f)) * c + rnd(sigmoid(i)) * rnd(np.tanh(g)))
        h = rnd(rnd(sigmoid(o)) * rnd(np.tanh(c)))
        hs.append(h)
    return np.stack(hs, axis=1)


def forward(p: dict, z: np.ndarray, rnd) -> np.ndarray:
    """(Z, W, M) standardised window -> (Z, M) network output."""
    h = lstm(z, p["Wx"], p["Wh"], p["b"], rnd)[:, -1]
    return rnd(rnd(matvec(np.maximum(h, 0.0), p["Wo"])) + p["bo"])
