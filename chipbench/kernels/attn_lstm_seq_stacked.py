"""``attn_lstm_seq_stacked`` (src/repro/kernels/attn_lstm_seq.py): the
fused per-target Attention-Double-LSTM forward plus head.

FLOPs and bytes count what the algorithm needs, from the shapes, with
zero initial state in both LSTMs (no ``h @ Wh`` on their first step, and
no recurrent weights at all at window 1): LSTM 1 over the window, the
``Wa`` query, the scores and the reweighting, LSTM 2 over the reweighted
sequence, the head.  Multiply-adds count 2; softmax and gate
nonlinearities are not counted.  Weights are float32, each read once.
"""
from __future__ import annotations

from chipbench.tracing import forecast_call_targets

DTYPE_BYTES = 4


def flops_per_target(hidden: int, n_metrics: int, window: int) -> int:
    H, M, W = hidden, n_metrics, window
    lstm1 = 8 * H * M + (W - 1) * 8 * H * (M + H)
    query = 2 * H * H
    scores = 2 * W * H
    reweight = W * H
    lstm2 = 8 * H * H + (W - 1) * 8 * H * (H + H)
    head = 2 * H * M
    return lstm1 + query + scores + reweight + lstm2 + head


def bytes_per_target(hidden: int, n_metrics: int, window: int) -> int:
    H, M, W = hidden, n_metrics, window
    weights = (M * 4 * H + 4 * H          # Wx1, b1
               + H * H                    # Wa
               + H * 4 * H + 4 * H        # Wx2, b2
               + H * M + M)               # Wo, bo
    if W > 1:
        weights += 2 * H * 4 * H          # Wh1, Wh2, needed from step 2
    io = W * M + M
    return DTYPE_BYTES * (weights + io)


def call_targets(event_name: str, hidden: int, n_metrics: int,
                 window: int) -> int | None:
    """Targets in one call, where a device-trace op is this kernel: the
    Mosaic custom call that reads the window (n, W, M) and writes the
    forecast (n, M)."""
    return forecast_call_targets(event_name, window, n_metrics)
