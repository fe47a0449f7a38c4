"""Per-target LSTM forecaster (arXiv:2112.10127 section 5.3.1): one LSTM
layer over the window from zero state, then a ReLU-dense head.

Plain reference copied from ``src/repro/kernels/ref.py`` (``lstm_seq``),
in numpy over a leading target axis and a tick axis after it (the windows
of several ticks of one target share its weights), float64 unless ``rnd``
rounds."""
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
    """Per-target ``x[z, t] @ w[z]``: (Z, T, K) x (Z, K, N) -> (Z, T, N),
    one (T, K) x (K, N) product a target."""
    return x @ w


def lstm(xs, Wx, Wh, b, rnd):
    """Hidden states (Z, T, W, H) of one LSTM layer over xs (Z, T, W, K),
    gates in the order i, f, g, o.  The input term of every step is one
    product a target over all ticks and steps, rounded as each step's."""
    Z, T, W, K = xs.shape
    H = Wh.shape[1]
    xw = rnd(matvec(xs.reshape(Z, T * W, K), Wx)).reshape(Z, T, W, 4 * H)
    b = b[:, None, :]
    h = np.zeros((Z, T, H))
    c = np.zeros((Z, T, H))
    hs = []
    for t in range(W):
        # from the zero state the recurrent term is exactly 0
        rec = rnd(matvec(h, Wh)) if t else 0.0
        gates = rnd(xw[:, :, t] + rec + b)
        i, f, g, o = np.split(gates, 4, axis=-1)
        c = rnd(rnd(sigmoid(f)) * c + rnd(sigmoid(i)) * rnd(np.tanh(g)))
        h = rnd(rnd(sigmoid(o)) * rnd(np.tanh(c)))
        hs.append(h)
    return np.stack(hs, axis=2)


def forward(p: dict, z: np.ndarray, rnd) -> np.ndarray:
    """(Z, T, W, M) standardised windows -> (Z, T, M) network output."""
    h = lstm(z, p["Wx"], p["Wh"], p["b"], rnd)[:, :, -1]
    return rnd(rnd(matvec(np.maximum(h, 0.0), p["Wo"])) + p["bo"][:, None])
