"""Fused block-batched Attention-Double-LSTM *sequence* kernel (Pallas) —
the second-generation forecast hot path of the PPA control plane.

``lstm_seq.py`` fused the plain whole-window LSTM; this module fuses the
Attention-Double-LSTM architecture (PAPERS.md, "Mitigating Temporal
Blindness in Kubernetes Autoscaling"): per block of batch rows, ONE
``pallas_call`` runs

1. the first LSTM pass over the W-step window, writing every hidden state
   into a (block_b, W, H) VMEM scratch history next to the (h, c)
   registers;
2. window-length temporal attention over that history — the query
   projection, the scaled-dot scores, the softmax and the reweighted
   context sequence all stay resident in VMEM (the window is small enough
   that nothing spills to HBM);
3. the second LSTM pass over the reweighted sequence plus the ReLU-dense
   head.

Two layouts, mirroring ``lstm_seq``:

* ``attn_lstm_seq``          — shared weights: xs (B, W, M) -> (B, n_out);
  gate/attention matmuls are plain GEMMs on the MXU;
* ``attn_lstm_seq_stacked``  — per-row weights with a leading target axis:
  xs (Z, W, M), every param leaf (Z, ...) -> (Z, n_out); matmuls are
  per-row GEMVs (``lstm_seq.row_matvec``) — Z independently trained
  per-target forecasters in ONE dispatch.

Both carry the checkpoint-style ``jax.custom_vjp``: the forward saves only
its inputs and the backward replays the pure-jnp reference
(``ref.attn_lstm_seq``) under ``jax.vjp`` — gradients are exactly those of
the non-Pallas formulation, so the fit paths (``_lstm_fit`` /
``lstm_fit_batch_stacked``) train through the kernel unchanged.  On CPU the
kernels run with ``interpret=True`` (CI parity vs ``ref.py``); on TPU they
compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref
from repro.kernels.lstm_seq import _F32, _gates_step as _gates, _pad_rows, \
    row_matvec


def _attn_seq_kernel(xs_ref, wx1_ref, wh1_ref, b1_ref, wa_ref, wx2_ref,
                     wh2_ref, b2_ref, wo_ref, bo_ref, out_ref,
                     h_ref, c_ref, hs_ref, *, window, hidden):
    """Shared-weights block: xs (bb, W, M); weights whole in VMEM; the
    hidden-state history, attention scores/softmax and reweighted context
    never leave VMEM."""
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    wx1 = wx1_ref[...]
    wh1 = wh1_ref[...]
    b1 = b1_ref[...].astype(jnp.float32)
    wx2 = wx2_ref[...]
    wh2 = wh2_ref[...]
    b2 = b2_ref[...].astype(jnp.float32)

    def step1(t, carry):
        # timesteps are read from refs: Mosaic lowers no dynamic_slice of
        # a loaded value
        x = xs_ref[:, t, :].astype(jnp.float32)
        gx = jax.lax.dot(x, wx1, precision=_F32,
                         preferred_element_type=jnp.float32)
        gh = jax.lax.dot(h_ref[...], wh1, precision=_F32,
                         preferred_element_type=jnp.float32)
        h2, c2 = _gates(c_ref[...], gx, gh, b1, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        hs_ref[:, pl.ds(t, 1), :] = h2[:, None, :]
        return carry

    jax.lax.fori_loop(0, window, step1, 0)

    # temporal attention over the in-VMEM hidden history
    hs = hs_ref[...]                                     # (bb, W, H)
    q = jax.lax.dot(h_ref[...], wa_ref[...], precision=_F32,
                    preferred_element_type=jnp.float32)  # (bb, H)
    scores = jnp.sum(hs * q[:, None, :], axis=-1) * (hidden ** -0.5)
    alpha = jax.nn.softmax(scores, axis=-1)              # (bb, W)
    # the reweighted context sequence replaces the history in its scratch
    hs_ref[...] = alpha[:, :, None] * hs                 # (bb, W, H)

    # second LSTM pass over the reweighted sequence (reuse (h, c) scratch)
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)

    def step2(t, carry):
        a = hs_ref[:, t, :]
        gx = jax.lax.dot(a, wx2, precision=_F32,
                         preferred_element_type=jnp.float32)
        gh = jax.lax.dot(h_ref[...], wh2, precision=_F32,
                         preferred_element_type=jnp.float32)
        h2, c2 = _gates(c_ref[...], gx, gh, b2, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        return carry

    jax.lax.fori_loop(0, window, step2, 0)
    head = jax.lax.dot(jax.nn.relu(h_ref[...]), wo_ref[...], precision=_F32,
                       preferred_element_type=jnp.float32)
    out_ref[...] = (head + bo_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _attn_seq_stacked_kernel(xs_ref, wx1_ref, wh1_ref, b1_ref, wa_ref,
                             wx2_ref, wh2_ref, b2_ref, wo_ref, bo_ref,
                             out_ref, h_ref, c_ref, hs_ref,
                             *, window, hidden):
    """Per-row-weights block: xs (bb, W, M), weight leaves (bb, ...); gate,
    query and head matmuls are per-row GEMVs over the whole block."""
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    b1 = b1_ref[...].astype(jnp.float32)
    b2 = b2_ref[...].astype(jnp.float32)

    def step1(t, carry):
        # weights are read from their refs at each use: a loaded copy held
        # live across the loop would be a second VMEM buffer per weight
        x = xs_ref[:, t, :].astype(jnp.float32)
        gx = row_matvec(x, wx1_ref[...])
        gh = row_matvec(h_ref[...], wh1_ref[...])
        h2, c2 = _gates(c_ref[...], gx, gh, b1, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        hs_ref[:, pl.ds(t, 1), :] = h2[:, None, :]
        return carry

    jax.lax.fori_loop(0, window, step1, 0)

    hs = hs_ref[...]                                     # (bb, W, H)
    q = row_matvec(h_ref[...], wa_ref[...])
    scores = jnp.sum(hs * q[:, None, :], axis=-1) * (hidden ** -0.5)
    alpha = jax.nn.softmax(scores, axis=-1)
    hs_ref[...] = alpha[:, :, None] * hs

    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)

    def step2(t, carry):
        a = hs_ref[:, t, :]
        gx = row_matvec(a, wx2_ref[...])
        gh = row_matvec(h_ref[...], wh2_ref[...])
        h2, c2 = _gates(c_ref[...], gx, gh, b2, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        return carry

    jax.lax.fori_loop(0, window, step2, 0)
    head = row_matvec(jax.nn.relu(h_ref[...]), wo_ref[...])
    out_ref[...] = (head + bo_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _attn_seq_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                     *, block_b, interpret):
    B, W, M = xs.shape
    H = Wh1.shape[0]
    n_out = Wo.shape[1]
    if B == 0:          # empty batch: match the scan path's contract
        return jnp.zeros((0, n_out), xs.dtype)
    block_b = max(min(block_b, B), 1)
    pad = (-B) % block_b
    xs, = _pad_rows([xs], pad)
    nb = xs.shape[0] // block_b
    # (1, N) bias rows, as in lstm_seq: the fit path vmaps this kernel
    kernel = functools.partial(_attn_seq_kernel, window=W, hidden=H)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, W, M), lambda i: (i, 0, 0)),
            pl.BlockSpec((M, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, n_out), lambda i: (0, 0)),
            pl.BlockSpec((1, n_out), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], n_out), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, W, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="attn_lstm_seq",
    )(xs, Wx1, Wh1, b1.reshape(1, -1), Wa, Wx2, Wh2, b2.reshape(1, -1), Wo,
      bo.reshape(1, -1))
    return out[:B]


def _attn_seq_stacked_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                             *, block_b, interpret):
    Z, W, M = xs.shape
    H = Wh1.shape[1]
    n_out = Wo.shape[2]
    if Z == 0:          # empty batch: match the vmap path's contract
        return jnp.zeros((0, n_out), xs.dtype)
    block_b = max(min(block_b, Z), 1)
    pad = (-Z) % block_b
    xs, Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo = _pad_rows(
        [xs, Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo], pad)
    nb = xs.shape[0] // block_b
    kernel = functools.partial(_attn_seq_stacked_kernel, window=W, hidden=H)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, W, M), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, M, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, H, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, 4 * H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H, H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, H, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, H, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, 4 * H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H, n_out), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], n_out), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, W, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="attn_lstm_seq_stacked",
    )(xs, Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo)
    return out[:Z]


# ------------------------------------------------------------- autodiff ---
# Checkpoint-style custom VJP, identical in shape to lstm_seq's: forward =
# the fused kernel, residuals = the raw inputs, backward = jax.vjp over the
# pure-jnp reference — no hand-written backward kernel, gradients exactly
# the non-Pallas formulation's.

@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _attn_seq_vjp(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                  block_b, interpret):
    return _attn_seq_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                            block_b=block_b, interpret=interpret)


def _attn_seq_fwd(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                  block_b, interpret):
    out = _attn_seq_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                           block_b=block_b, interpret=interpret)
    return out, (Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs)


def _attn_seq_bwd(block_b, interpret, res, g):
    _, vjp = jax.vjp(ref.attn_lstm_seq, *res)
    return vjp(g)


_attn_seq_vjp.defvjp(_attn_seq_fwd, _attn_seq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(10, 11))
def _attn_seq_stacked_vjp(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                          block_b, interpret):
    return _attn_seq_stacked_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo,
                                    xs, block_b=block_b, interpret=interpret)


def _attn_seq_stacked_fwd(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                          block_b, interpret):
    out = _attn_seq_stacked_pallas(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo,
                                   xs, block_b=block_b, interpret=interpret)
    return out, (Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs)


def _attn_seq_stacked_bwd(block_b, interpret, res, g):
    _, vjp = jax.vjp(ref.attn_lstm_seq_stacked, *res)
    return vjp(g)


_attn_seq_stacked_vjp.defvjp(_attn_seq_stacked_fwd, _attn_seq_stacked_bwd)


# --------------------------------------------------------------- public ---
def attn_lstm_seq(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                  *, block_b: int = 128, interpret: bool = False):
    """xs (B, W, M); Wx1 (M, 4H); Wh1/Wh2 (H, 4H); Wa (H, H); Wx2 (H, 4H);
    b1/b2 (4H,); Wo (H, n_out); bo (n_out,) -> (B, n_out).  Whole-window
    Attention-Double-LSTM + ReLU-dense head, one fused kernel;
    differentiable (checkpoint-style custom VJP)."""
    return _attn_seq_vjp(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                         block_b, interpret)


def attn_lstm_seq_stacked(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo, xs,
                          *, block_b: int = 32, interpret: bool = False):
    """Per-target layout: xs (Z, W, M) and a leading Z axis on every weight
    leaf -> (Z, n_out).  Z independently parameterised Attention-Double-
    LSTMs answered by ONE fused kernel (per-row GEMV matmuls per block)."""
    return _attn_seq_stacked_vjp(Wx1, Wh1, b1, Wa, Wx2, Wh2, b2, Wo, bo,
                                 xs, block_b, interpret)
