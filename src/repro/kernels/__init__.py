# Pallas TPU kernels for the serving/training substrate's compute hot spots
# (+ ops.py jit wrappers, ref.py pure-jnp oracles).  Validated on CPU with
# interpret=True; TPU is the compile target (BlockSpec/VMEM tiling).
#
# The public fused-kernel entry points are re-exported here so callers can
# write ``from repro.kernels import lstm_seq, attn_lstm_seq`` instead of
# deep-module imports.  The assignments below intentionally rebind the
# package attributes the import system pointed at the implementation
# submodules of the same name, so those names are the jitted callables —
# internal code therefore imports implementations by full module path
# (see ops.py), never through package attributes.
from repro.kernels import ref
from repro.kernels import ops as _ops

flash_attention = _ops.flash_attention
decode_attention = _ops.decode_attention
ssd_scan = _ops.ssd_scan
lstm_cell = _ops.lstm_cell
lstm_seq = _ops.lstm_seq
lstm_seq_stacked = _ops.lstm_seq_stacked
attn_lstm_seq = _ops.attn_lstm_seq
attn_lstm_seq_stacked = _ops.attn_lstm_seq_stacked
rmsnorm = _ops.rmsnorm

__all__ = [
    "ref",
    "flash_attention", "decode_attention", "ssd_scan", "lstm_cell",
    "lstm_seq", "lstm_seq_stacked",
    "attn_lstm_seq", "attn_lstm_seq_stacked",
    "rmsnorm",
]
