"""Fused LSTM cell for TPU (Pallas) — the PPA forecaster's hot loop.

One kernel fuses both gate matmuls (x·Wx + h·Wh + b) and the four gate
nonlinearities, so the (B, 4H) gate tensor never round-trips through HBM
(the Keras/XLA version materialises it).  Batch rows are tiled on the grid;
weights are small enough (H=50 for the paper's model) to sit whole in VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, h_ref, c_ref, wx_ref, wh_ref, b_ref, h2_ref, c2_ref, *,
            hidden):
    x = x_ref[...]
    h = h_ref[...]
    c = c_ref[...].astype(jnp.float32)
    gates = (jax.lax.dot(x, wx_ref[...], preferred_element_type=jnp.float32)
             + jax.lax.dot(h, wh_ref[...], preferred_element_type=jnp.float32)
             + b_ref[...].astype(jnp.float32))
    i = jax.nn.sigmoid(gates[:, 0 * hidden:1 * hidden])
    f = jax.nn.sigmoid(gates[:, 1 * hidden:2 * hidden])
    g = jnp.tanh(gates[:, 2 * hidden:3 * hidden])
    o = jax.nn.sigmoid(gates[:, 3 * hidden:4 * hidden])
    c2 = f * c + i * g
    h2 = o * jnp.tanh(c2)
    h2_ref[...] = h2.astype(h2_ref.dtype)
    c2_ref[...] = c2.astype(c2_ref.dtype)


def lstm_cell(Wx, Wh, b, h, c, x, *, block_b=128, interpret=False):
    """x (B, In); h, c (B, H); Wx (In, 4H); Wh (H, 4H); b (4H,)
    -> (h', c')."""
    B, In = x.shape
    H = Wh.shape[0]
    block_b = min(block_b, B)
    pad = (-B) % block_b
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        h = jnp.pad(h, ((0, pad), (0, 0)))
        c = jnp.pad(c, ((0, pad), (0, 0)))
    nb = x.shape[0] // block_b
    kernel = functools.partial(_kernel, hidden=H)
    h2, c2 = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_b, In), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
            pl.BlockSpec((In, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((H, 4 * H), lambda i: (0, 0)),
            pl.BlockSpec((4 * H,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
            pl.BlockSpec((block_b, H), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((x.shape[0], H), h.dtype),
            jax.ShapeDtypeStruct((x.shape[0], H), c.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, h, c, Wx, Wh, b)
    return h2[:B], c2[:B]
