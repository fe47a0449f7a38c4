"""jit'd public wrappers for the Pallas kernels.

On this CPU container the kernels run with ``interpret=True`` (Pallas
executes the kernel body in Python for numerical validation); on a TPU
backend they compile to Mosaic.  ``KERNEL_INTERPRET`` can be forced for
tests.
"""
from __future__ import annotations

import functools

import jax

# Direct-from-module imports (not package-attribute submodule imports):
# the package __init__ rebinds names like ``lstm_seq`` to these jitted
# wrappers, so the submodule attributes of the same name must never be
# relied on after package init.
from repro.kernels.flash_attention import flash_attention as _fa_impl
from repro.kernels.decode_attention import decode_attention as _da_impl
from repro.kernels.ssd_scan import ssd_scan as _ssd_impl
from repro.kernels.lstm_cell import lstm_cell as _lstm_cell_impl
from repro.kernels.lstm_seq import (
    lstm_seq as _lseq_impl,
    lstm_seq_stacked as _lseq_stacked_impl,
    lstm_seq_stacked_form as _lseq_form_impl)
from repro.kernels.attn_lstm_seq import (
    attn_lstm_seq as _aseq_impl,
    attn_lstm_seq_stacked as _aseq_stacked_impl)
from repro.kernels.rmsnorm import rmsnorm as _rms_impl


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "cap", "q_offset", "kv_valid", "scale",
    "block_q", "block_kv"))
def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    q_offset=0, kv_valid=None, scale=None,
                    block_q=128, block_kv=128):
    return _fa_impl(
        q, k, v, causal=causal, window=window, cap=cap, q_offset=q_offset,
        kv_valid=kv_valid, scale=scale, block_q=block_q, block_kv=block_kv,
        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("cap", "window", "scale",
                                             "block_s"))
def decode_attention(q, k, v, kv_valid, *, cap=None, window=None, scale=None,
                     block_s=256):
    return _da_impl(q, k, v, kv_valid=kv_valid, cap=cap,
                    window=window, scale=scale, block_s=block_s,
                    interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk=128):
    return _ssd_impl(x, dt, A, Bm, Cm, D, chunk=chunk,
                     interpret=_interpret())


@jax.jit
def lstm_cell(Wx, Wh, b, h, c, x):
    return _lstm_cell_impl(Wx, Wh, b, h, c, x, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_b",))
def lstm_seq(Wx, Wh, b, Wo, bo, xs, *, block_b=128):
    """Fused whole-window LSTM + ReLU-dense head, shared weights:
    xs (B, W, M) -> (B, n_out).  Differentiable (custom VJP)."""
    return _lseq_impl(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                      interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_b",))
def lstm_seq_stacked(Wx, Wh, b, Wo, bo, xs, *, block_b=None):
    """Fused whole-window forward for Z stacked per-target LSTMs (leading
    Z axis on xs and every weight leaf) — ONE kernel dispatch per tick."""
    return _lseq_stacked_impl(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                              interpret=_interpret())


def lstm_seq_stacked_local(form, xs, *, block_b=None):
    """Unjitted stacked LSTM forward over weights in the stacked form
    (``lstm_seq.stacked_form``), for callers that own the jit boundary —
    in particular ``shard_map`` programs (the multi-device control plane,
    core/device_plane.py), where the kernel must trace on the per-device
    LOCAL block shapes rather than behind a nested jit.  Backend interpret
    resolution is identical to the jitted wrapper."""
    return _lseq_form_impl(form, xs, block_b=block_b,
                           interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_b",))
def attn_lstm_seq(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs, *,
                  block_b=128):
    """Fused Attention-Double-LSTM + ReLU-dense head, shared weights:
    xs (B, W, M) -> (B, n_out).  Differentiable (custom VJP)."""
    return _aseq_impl(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                      block_b=block_b, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_b",))
def attn_lstm_seq_stacked(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs, *,
                          block_b=32):
    """Fused Attention-Double-LSTM forward for Z stacked per-target models
    (leading Z axis on xs and every weight leaf) — ONE kernel dispatch per
    tick per shard."""
    return _aseq_stacked_impl(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                              block_b=block_b, interpret=_interpret())


def attn_lstm_seq_stacked_local(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                                *, block_b=32):
    """Unjitted ``attn_lstm_seq_stacked`` body for callers that own the jit
    boundary (``shard_map`` programs — the multi-device control plane),
    mirroring ``lstm_seq_stacked_local``."""
    return _aseq_stacked_impl(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                              block_b=block_b, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, w, *, eps=1e-6):
    return _rms_impl(x, w, eps=eps, interpret=_interpret())
