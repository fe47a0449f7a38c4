"""``lstm_seq_stacked`` (src/repro/kernels/lstm_seq.py): the fused
per-target LSTM forward over the window plus the ReLU-dense head, one row
of weights per target.

FLOPs and bytes count what the algorithm needs, from the shapes, whatever
implements it.  The state starts at zero, so the first step has no
``h @ Wh`` term; at window 1 the recurrent weights are never needed and
neither their FLOPs nor their bytes are counted.  Multiply-adds count 2;
gate nonlinearities are not counted.  Weights are float32, each read once.
"""
from __future__ import annotations

from chipbench.tracing import forecast_call_targets

DTYPE_BYTES = 4


def flops_per_target(hidden: int, n_metrics: int, window: int) -> int:
    H, M, W = hidden, n_metrics, window
    lstm = 8 * H * M + (W - 1) * 8 * H * (M + H)
    head = 2 * H * M
    return lstm + head


def bytes_per_target(hidden: int, n_metrics: int, window: int) -> int:
    H, M, W = hidden, n_metrics, window
    weights = M * 4 * H + 4 * H + H * M + M
    if W > 1:
        weights += H * 4 * H                    # Wh, needed from step 2
    io = W * M + M                              # window in, forecast out
    return DTYPE_BYTES * (weights + io)


def call_targets(event_name: str, hidden: int, n_metrics: int,
                 window: int) -> int | None:
    """Targets in one call, where a device-trace op is this kernel: Pallas
    gives it no stable name, so it is the Mosaic custom call that reads
    the window (n, W, M) and writes the forecast (n, M)."""
    return forecast_call_targets(event_name, window, n_metrics)
