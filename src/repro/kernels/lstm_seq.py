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
  xs (Z, W, M), every param leaf (Z, ...) -> (Z, n_out) — Z independently
  trained per-target LSTMs in ONE dispatch.  At window 1 the kernel reads
  the weights in their *stacked form* (``stacked_form``): one f32 row per
  target holding Wx, b, Wo and bo in 128-lane-aligned blocks, and no Wh;
  past window 1 it reads the leaves as they are and its gate matmuls are
  per-row GEMVs (``row_matvec``: a VPU multiply and a reduce over K).
  The device plane installs these operands once per refit epoch; this
  entry builds them per call.

Both are differentiable via ``jax.custom_vjp`` with a checkpoint-style
backward: the forward saves only its inputs and the backward replays the
pure-jnp reference (``ref.lstm_seq``) under ``jax.vjp`` — gradients are
exactly those of the non-Pallas formulation, so the fit path
(``_lstm_fit`` / ``lstm_fit_batch_stacked``) trains through the kernel
unchanged.  On CPU the kernels run with ``interpret=True`` (CI parity
tests vs ``ref.py``); on TPU they compile to Mosaic.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

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


def _seq_form_kernel(xs_ref, theta_ref, out_ref, *, hidden, n_out):
    """Window-1 block in the stacked form: xs (bb, 1, M), theta (bb, P).
    The state starts at zero, so there is no recurrent term and no forget
    term: c = i * g.  Each per-row GEMV is a sum over K of an aligned
    weight row slice scaled by one input column."""
    n_in = xs_ref.shape[-1]
    H, G = hidden, 4 * hidden
    g_stride, h_stride = _lanes(G), _lanes(H)
    b, wo, bo, _ = _form_layout(n_in, H, n_out)
    x = xs_ref[:, 0, :].astype(jnp.float32)
    gates = x[:, 0:1] * theta_ref[:, 0:G]
    for k in range(1, n_in):
        gates = gates + x[:, k:k + 1] * theta_ref[:, k * g_stride:
                                                  k * g_stride + G]
    gates = gates + theta_ref[:, b:b + G]
    i = jax.nn.sigmoid(gates[:, 0:H])
    g = jnp.tanh(gates[:, 2 * H:3 * H])
    o = jax.nn.sigmoid(gates[:, 3 * H:G])
    hr = jax.nn.relu(o * jnp.tanh(i * g))
    # head: Wo lies as (n_out, H), so output j is a reduce over lanes
    lane = jax.lax.broadcasted_iota(jnp.int32, out_ref.shape, 1)
    out = theta_ref[:, bo:bo + n_out]
    for j in range(n_out):
        w = theta_ref[:, wo + j * h_stride:wo + j * h_stride + H]
        col = jnp.sum(hr * w, axis=1, keepdims=True)
        out = out + jnp.where(lane == j, col, 0.0)
    out_ref[...] = out.astype(out_ref.dtype)


# --------------------------------------------------------- stacked form ---
# At window 1 the per-target kernel reads one f32 row per target: Wx as M
# blocks of 4H, b as one block of 4H, Wo transposed as n_out blocks of H,
# bo, every block zero-padded to a multiple of 128 lanes, and no Wh: the
# state starts at zero, so ``h @ Wh`` is exactly zero.  Two reasons:
# * on a TPU the default layout of a (Z, P) array with P a multiple of 128
#   is the row-major tiled one the Mosaic call reads, so an installed form
#   reaches the kernel with no relayout; a stacked 3-D leaf such as Wh
#   (Z, 50, 200) defaults to the layout with the target axis minor, which
#   pads least but is not the kernel's, so XLA would copy it every call;
# * aligned blocks are read without lane rotations: on a v5e the kernel
#   took 1.5x as long with the leaves packed back to back.
# Past window 1 the recurrent body reads the leaves as they are.

_LANES = 128
# bytes of theta in one grid block: double-buffered, with the block's
# temporaries, well inside the 16 MiB of scoped VMEM of a v5e core
_BLOCK_BYTES = 2 << 20
_RECURRENT_BLOCK = 32
LEAVES = ("Wx", "Wh", "b", "Wo", "bo")


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["theta"], meta_fields=["hidden", "n_out"])
@dataclasses.dataclass(frozen=True)
class StackedForm:
    """Per-target LSTM weights as the window-1 kernel reads them:
    ``theta`` (Z, P) with one row per target (see ``stacked_form``)."""
    theta: Any
    hidden: int
    n_out: int


def _lanes(n: int) -> int:
    return -(-n // _LANES) * _LANES


def _form_layout(n_in: int, hidden: int, n_out: int):
    """Lane offsets of b, Wo and bo in a target's row (Wx is at 0), and
    the row's width P."""
    b = n_in * _lanes(4 * hidden)
    wo = b + _lanes(4 * hidden)
    bo = wo + n_out * _lanes(hidden)
    return b, wo, bo, bo + _lanes(n_out)


def form_width(n_in: int, hidden: int, n_out: int) -> int:
    """Lanes P of one target's row in the stacked form."""
    return _form_layout(n_in, hidden, n_out)[-1]


def stacked_form(leaf, window: int, *, xp=jnp):
    """The stacked kernel's weight operands at ``window``.  ``leaf(name)``
    gives the leaf of that name with its leading target axes.  At window
    1 a ``StackedForm``, for which ``Wh`` is never asked for; past window
    1 the leaves ``LEAVES`` as they are.  ``xp`` is ``jnp`` inside a
    program and ``np`` for an install built on the host."""
    if window > 1:
        return tuple(leaf(name) for name in LEAVES)
    Wx, b, Wo, bo = leaf("Wx"), leaf("b"), leaf("Wo"), leaf("bo")
    lead = Wx.shape[:-2]
    hidden, n_out = Wx.shape[-1] // 4, Wo.shape[-1]

    def blocks(a):
        # (..., K, N) -> (..., K * lanes(N)): each row zero-padded
        k, n = a.shape[-2:]
        pad = xp.zeros(a.shape[:-1] + (_lanes(n) - n,), a.dtype)
        return xp.concatenate([a, pad], axis=-1).reshape(
            lead + (k * _lanes(n),))

    theta = xp.concatenate(
        [blocks(Wx), blocks(b[..., None, :]),
         blocks(xp.swapaxes(Wo, -1, -2)), blocks(bo[..., None, :])], axis=-1)
    return StackedForm(theta, hidden, n_out)


def _block_rows(width: int) -> int:
    """Rows of a grid block: the power of two nearest below
    ``_BLOCK_BYTES`` of theta, at least 8."""
    rows = max(_BLOCK_BYTES // (4 * width), 8)
    return 1 << (rows.bit_length() - 1)


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


def _seq_form_pallas(form, xs, *, block_b, interpret):
    Z, W, M = xs.shape
    H, n_out = form.hidden, form.n_out
    theta = form.theta
    width = theta.shape[-1]
    if W != 1 or width != form_width(M, H, n_out):
        raise ValueError(f"a stacked form of width {width} does not fit "
                         f"M={M}, H={H}, n_out={n_out} at window {W}")
    if Z == 0:          # empty batch: match the vmap path's contract
        return jnp.zeros((0, n_out), xs.dtype)
    block_b = max(min(block_b, Z), 1)
    pad = (-Z) % block_b
    xs, theta = _pad_rows([xs, theta], pad)
    nb = xs.shape[0] // block_b
    kernel = functools.partial(_seq_form_kernel, hidden=H, n_out=n_out)
    out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, W, M), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_b, width), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, n_out), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((xs.shape[0], n_out), xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name="lstm_seq_stacked",
    )(xs, theta)
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


def _leaves_form(Wx, Wh, b, Wo, bo, xs):
    leaves = dict(zip(LEAVES, (Wx, Wh, b, Wo, bo)))
    return stacked_form(leaves.__getitem__, xs.shape[1])


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _lstm_seq_stacked_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    return lstm_seq_stacked_form(_leaves_form(Wx, Wh, b, Wo, bo, xs), xs,
                                 block_b=block_b, interpret=interpret)


def _lstm_seq_stacked_fwd(Wx, Wh, b, Wo, bo, xs, block_b, interpret):
    out = lstm_seq_stacked_form(_leaves_form(Wx, Wh, b, Wo, bo, xs), xs,
                                block_b=block_b, interpret=interpret)
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


def lstm_seq_stacked(Wx, Wh, b, Wo, bo, xs, *, block_b: int | None = None,
                     interpret: bool = False):
    """Per-target layout: xs (Z, W, M) and a leading Z axis on every weight
    leaf -> (Z, n_out).  Z independently parameterised LSTMs answered by
    ONE fused kernel (per-row GEMV gate matmuls per block); the leaves are
    put in the stacked form on each call (``lstm_seq_stacked_form``).
    Differentiable (checkpoint-style custom VJP)."""
    return _lstm_seq_stacked_vjp(Wx, Wh, b, Wo, bo, xs, block_b, interpret)


def lstm_seq_stacked_form(form, xs, *, block_b: int | None = None,
                          interpret: bool = False):
    """``lstm_seq_stacked`` over the weight operands of ``stacked_form``
    (the device plane installs them once per refit epoch): xs (Z, W, M)
    -> (Z, n_out).  The window's static shape picks the body: at window 1
    the stacked form, with no recurrent term; past it the leaves as they
    are.  ``block_b`` rows per grid block, by default ``_BLOCK_BYTES`` of
    the form at window 1 and ``_RECURRENT_BLOCK`` past it.  Forward
    only."""
    if xs.shape[1] > 1:
        return _seq_stacked_pallas(*form, xs,
                                   block_b=block_b or _RECURRENT_BLOCK,
                                   interpret=interpret)
    return _seq_form_pallas(
        form, xs, block_b=block_b or _block_rows(form.theta.shape[-1]),
        interpret=interpret)
