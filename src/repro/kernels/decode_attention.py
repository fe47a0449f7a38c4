"""Flash-decode for TPU (Pallas): single-query attention against a long KV
cache.  Grid = (B, Hq, ns) with the cache-sequence axis last (sequential);
the (m, l, acc) running state is carried in VMEM scratch across cache
blocks, so an arbitrarily long cache streams through a fixed VMEM budget.
kv_valid masks cache padding (per batch row)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _kernel(valid_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, cap, window, block_s, ns):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0]                                   # (1, D) row
    k = k_ref[0]                                   # (bs, D)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if cap is not None:
        s = cap * jnp.tanh(s / cap)                # (1, bs)
    valid = valid_ref[0]
    k_pos = j * block_s + jax.lax.broadcasted_iota(jnp.int32, (1, block_s), 1)
    mask = k_pos < valid
    if window is not None:
        mask &= (valid - 1 - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
    pv = jax.lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
    m_ref[...] = m_new

    @pl.when(j == ns - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0, 0] = out[0].astype(o_ref.dtype)


def decode_attention(q, k, v, *, kv_valid, cap=None, window=None, scale=None,
                     block_s=256, interpret=False):
    """q (B, Hq, D); k, v (B, Hkv, S, D); kv_valid (B,) int32
    -> (B, Hq, D)."""
    B, Hq, D = q.shape
    _, Hkv, S, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_s = min(block_s, S)
    assert S % block_s == 0
    ns = S // block_s
    valid = jnp.broadcast_to(jnp.asarray(kv_valid, jnp.int32).reshape(-1),
                             (B,)).reshape(B, 1)

    kernel = functools.partial(_kernel, scale=scale, cap=cap, window=window,
                               block_s=block_s, ns=ns)
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, ns),
        in_specs=[
            pl.BlockSpec((1, 1), lambda b, h, j: (b, 0)),
            pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
            pl.BlockSpec((1, block_s, D), lambda b, h, j, G=G: (b * (k.shape[1]) + h // G, j, 0)),
            pl.BlockSpec((1, block_s, D), lambda b, h, j, G=G: (b * (k.shape[1]) + h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, D), lambda b, h, j: (b, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(valid, q.reshape(B, Hq, D), k.reshape(B * Hkv, S, D),
      v.reshape(B * Hkv, S, D))
    return out
