"""Per-target Attention-Double-LSTM forecaster (arXiv:2603.28790, as
``src/repro/core/forecaster.py`` implements it): an LSTM over the window,
temporal attention whose query is the last hidden state projected by
``Wa``, a second LSTM over the reweighted hidden sequence, a ReLU-dense
head.

Plain reference copied from ``src/repro/kernels/ref.py``
(``attn_lstm_seq``), in numpy over a leading target axis and a tick axis
after it, as ``models/lstm.py``."""
from __future__ import annotations

import numpy as np

from chipbench.models.lstm import lstm, matvec

PROGRAM_CLASS = "AttnLSTMForecaster"   # in repro.core.forecaster


def leaf_shapes(hidden: int, n_metrics: int) -> dict:
    H, M = hidden, n_metrics
    return {"Wx1": (M, 4 * H), "Wh1": (H, 4 * H), "b1": (4 * H,),
            "Wa": (H, H),
            "Wx2": (H, 4 * H), "Wh2": (H, 4 * H), "b2": (4 * H,),
            "Wo": (H, M), "bo": (M,)}


def forward(p: dict, z: np.ndarray, rnd) -> np.ndarray:
    """(Z, T, W, M) standardised windows -> (Z, T, M) network output."""
    hs = lstm(z, p["Wx1"], p["Wh1"], p["b1"], rnd)      # (Z, T, W, H)
    H = hs.shape[-1]
    q = rnd(matvec(hs[:, :, -1], p["Wa"]))              # (Z, T, H)
    scores = rnd(rnd(np.einsum("ztwh,zth->ztw", hs, q)) * H ** -0.5)
    e = np.exp(scores - scores.max(axis=-1, keepdims=True))
    alpha = rnd(e / e.sum(axis=-1, keepdims=True))            # (Z, T, W)
    ctx = rnd(alpha[..., None] * hs)                          # (Z, T, W, H)
    h2 = lstm(ctx, p["Wx2"], p["Wh2"], p["b2"], rnd)[:, :, -1]
    return rnd(rnd(matvec(np.maximum(h2, 0.0), p["Wo"])) + p["bo"][:, None])
