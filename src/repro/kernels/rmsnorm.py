"""Fused RMSNorm for TPU (Pallas): one pass — f32 variance reduction and
scale applied in VMEM, bf16 in/out (the XLA path materialises the f32
upcast; see EXPERIMENTS.md §Perf iteration 1)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)            # (rows, D)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, w, *, eps=1e-6, block_rows=256, interpret=False):
    """x (R, D), w (D,) -> (R, D)."""
    R, D = x.shape
    block_rows = min(block_rows, R)
    pad = (-R) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    nb = x.shape[0] // block_rows
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
            pl.BlockSpec((D,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((x.shape[0], D), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
    return out[:R]
