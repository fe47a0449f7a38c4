"""Fused block-batched LSTM *sequence* kernel for TPU (Pallas) — the
stacked forecast/fit hot path of the PPA control plane.

``lstm_cell.py`` fuses one timestep; the stacked per-target forward
(``_lstm_forward_stacked``) still re-dispatched it W times per tick through
a vmapped ``lax.scan``, so a Z-target tick cost Z×W kernel launches and the
(h, c) state round-tripped through HBM between steps.  This module fuses
the WHOLE window: one ``pallas_call`` grids over batch blocks (``block_b``
rows = stacked Z targets, E×Z ensemble members, or N training windows),
keeps (h, c) resident in VMEM scratch across an in-kernel ``fori_loop``
over the W timesteps, and fuses the input/hidden GEMMs, the four gate
nonlinearities and the ReLU-dense head per block — one kernel per tick per
shard.

Two layouts:

* ``lstm_seq``          — shared weights: xs (B, W, M) -> (B, n_out); the
  gate matmuls are plain (B, M)@(M, 4H) GEMMs on the MXU (the shared-model
  ``predict_batch`` and every fit-path forward);
* ``lstm_seq_stacked``  — per-row weights with a leading target axis:
  xs (Z, W, M), every param leaf (Z, ...) -> (Z, n_out); the gate matmuls
  are per-row GEMVs (``row_matvec``: a VPU multiply and a reduce over K)
  — Z independently trained per-target LSTMs in ONE dispatch.

Both are differentiable via ``jax.custom_vjp`` with a checkpoint-style
backward: the forward saves only its inputs and the backward replays the
pure-jnp reference (``ref.lstm_seq``) under ``jax.vjp`` — gradients are
exactly those of the non-Pallas formulation, so the fit path
(``_lstm_fit`` / ``lstm_fit_batch_stacked``) trains through the kernel
unchanged.  On CPU the kernels run with ``interpret=True`` (CI parity
tests vs ``ref.py``); on TPU they compile to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.kernels import ref

# f32 in, f32 out: the kernels keep the forecaster's f32 contract on the MXU
_F32 = jax.lax.Precision.HIGHEST


def row_matvec(x, w):
    """Per-row weights: x (bb, K) and w (bb, K, N) -> (bb, N), row r being
    ``x[r] @ w[r]``.  Written as a multiply and a reduce over K in f32:
    Mosaic cannot encode the batched ``dot_general`` form, whose (bb, K)
    lhs has no non-contracting dimension."""
    return jnp.sum(x[:, :, None] * w, axis=1)


def _gates_step(c, gx, gh, b, *, hidden):
    """Shared gate math: pre-activations -> (h', c') in f32."""
    gates = gx + gh + b
    i = jax.nn.sigmoid(gates[:, 0 * hidden:1 * hidden])
    f = jax.nn.sigmoid(gates[:, 1 * hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:4 * hidden])
    c2 = f * c + i * g
    return o * jnp.tanh(c2), c2


def _seq_kernel(xs_ref, wx_ref, wh_ref, b_ref, wo_ref, bo_ref, out_ref,
                h_ref, c_ref, *, window, hidden):
    """Shared-weights block: xs (bb, W, M); weights whole in VMEM."""
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    wx = wx_ref[...]
    wh = wh_ref[...]
    b = b_ref[...].astype(jnp.float32)

    def step(t, carry):
        # timestep read from the ref: Mosaic lowers no dynamic_slice of a
        # loaded value
        x = xs_ref[:, t, :].astype(jnp.float32)
        gx = jax.lax.dot(x, wx, precision=_F32,
                         preferred_element_type=jnp.float32)
        gh = jax.lax.dot(h_ref[...], wh, precision=_F32,
                         preferred_element_type=jnp.float32)
        h2, c2 = _gates_step(c_ref[...], gx, gh, b, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        return carry

    jax.lax.fori_loop(0, window, step, 0)
    head = jax.lax.dot(jax.nn.relu(h_ref[...]), wo_ref[...], precision=_F32,
                       preferred_element_type=jnp.float32)
    out_ref[...] = (head + bo_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _seq_stacked_kernel(xs_ref, wx_ref, wh_ref, b_ref, wo_ref, bo_ref,
                        out_ref, h_ref, c_ref, *, window, hidden):
    """Per-row-weights block: xs (bb, W, M), weight leaves (bb, ...); the
    gate matmuls are per-row GEMVs over the whole block."""
    h_ref[...] = jnp.zeros_like(h_ref)
    c_ref[...] = jnp.zeros_like(c_ref)
    b = b_ref[...].astype(jnp.float32)

    def step(t, carry):
        # weights are read from their refs at each use: a loaded copy held
        # live across the loop would be a second VMEM buffer per weight
        x = xs_ref[:, t, :].astype(jnp.float32)
        gx = row_matvec(x, wx_ref[...])
        gh = row_matvec(h_ref[...], wh_ref[...])
        h2, c2 = _gates_step(c_ref[...], gx, gh, b, hidden=hidden)
        h_ref[...] = h2
        c_ref[...] = c2
        return carry

    jax.lax.fori_loop(0, window, step, 0)
    head = row_matvec(jax.nn.relu(h_ref[...]), wo_ref[...])
    out_ref[...] = (head + bo_ref[...].astype(jnp.float32)
                    ).astype(out_ref.dtype)


def _pad_rows(arrs, pad: int):
    if not pad:
        return arrs
    return [jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
            for a in arrs]


def _seq_pallas(Wx, Wh, b, Wo, bo, xs, *, block_b, interpret):
    B, W, M = xs.shape
    H = Wh.shape[0]
    n_out = Wo.shape[1]
    if B == 0:          # empty batch: match the scan path's contract
        return jnp.zeros((0, n_out), xs.dtype)
    block_b = max(min(block_b, B), 1)
    pad = (-B) % block_b
    xs, = _pad_rows([xs], pad)
    nb = xs.shape[0] // block_b
    # biases go in as (1, N) rows: the fit path vmaps this kernel over
    # targets, and Mosaic refuses a block whose second-minor dimension is
    # a squeezed batch axis
    kernel = functools.partial(_seq_kernel, window=W, hidden=H)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, W, M), lambda i: (i, 0, 0)),
            pl.BlockSpec((M, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((1, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, n_out), lambda i: (0, 0)),
            pl.BlockSpec((1, n_out), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], n_out), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="lstm_seq",
    )(xs, Wx, Wh, b.reshape(1, -1), Wo, bo.reshape(1, -1))
    return out[:B]


def _seq_stacked_pallas(Wx, Wh, b, Wo, bo, xs, *, block_b, interpret):
    Z, W, M = xs.shape
    H = Wh.shape[1]
    n_out = Wo.shape[2]
    if Z == 0:          # empty batch: match the vmap path's contract
        return jnp.zeros((0, n_out), xs.dtype)
    block_b = max(min(block_b, Z), 1)
    pad = (-Z) % block_b
    xs, Wx, Wh, b, Wo, bo = _pad_rows([xs, Wx, Wh, b, Wo, bo], pad)
    nb = xs.shape[0] // block_b
    kernel = functools.partial(_seq_stacked_kernel, window=W, hidden=H)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, W, M), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, M, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, H, 4 * H), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, 4 * H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H, n_out), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], n_out), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_b, H), jnp.float32),
                        pltpu.VMEM((block_b, H), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="lstm_seq_stacked",
    )(xs, Wx, Wh, b, Wo, bo)
    return out[:Z]


# ------------------------------------------------------------- autodiff ---
# Checkpoint-style custom VJP: forward = the fused kernel, residuals = the
# raw inputs, backward = jax.vjp over the pure-jnp reference.  Gradients are
# exactly the non-Pallas formulation's (ref.lstm_seq is op-for-op the
# lax.scan forward), so the fit path differentiates through the kernel
# without a hand-written backward kernel.

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _lstm_seq_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    return _seq_pallas(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                       interpret=interpret)


def _lstm_seq_fwd(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    out = _seq_pallas(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                      interpret=interpret)
    return out, (Wx, Wh, b, Wo, bo, xs)


def _lstm_seq_bwd(block_b, interpret, res, g):
    _, vjp = jax.vjp(ref.lstm_seq, *res)
    return vjp(g)


_lstm_seq_vjp.defvjp(_lstm_seq_fwd, _lstm_seq_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _lstm_seq_stacked_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    return _seq_stacked_pallas(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                               interpret=interpret)


def _lstm_seq_stacked_fwd(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    out = _seq_stacked_pallas(Wx, Wh, b, Wo, bo, xs, block_b=block_b,
                              interpret=interpret)
    return out, (Wx, Wh, b, Wo, bo, xs)


def _lstm_seq_stacked_bwd(block_b, interpret, res, g):
    _, vjp = jax.vjp(ref.lstm_seq_stacked, *res)
    return vjp(g)


_lstm_seq_stacked_vjp.defvjp(_lstm_seq_stacked_fwd, _lstm_seq_stacked_bwd)


# --------------------------------------------------------------- public ---
def lstm_seq(Wx, Wh, b, Wo, bo, xs, *, block_b: int = 128,
             interpret: bool = False):
    """xs (B, W, M); Wx (M, 4H); Wh (H, 4H); b (4H,); Wo (H, n_out);
    bo (n_out,) -> (B, n_out).  Whole-window LSTM + ReLU-dense head, one
    fused kernel; differentiable (checkpoint-style custom VJP)."""
    return _lstm_seq_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret)


def lstm_seq_stacked(Wx, Wh, b, Wo, bo, xs, *, block_b: int = 32,
                     interpret: bool = False):
    """Per-target layout: xs (Z, W, M) and a leading Z axis on every weight
    leaf -> (Z, n_out).  Z independently parameterised LSTMs answered by
    ONE fused kernel (per-row GEMV gate matmuls per block)."""
    return _lstm_seq_stacked_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret)
