"""Flash attention for TPU (Pallas): online-softmax blocked attention with
GQA, causal / sliding-window masks and gemma2 logit soft-cap.

TPU-native layout: grid = (B·Hq, nq, nk) with the kv dimension LAST so it is
the sequential (``arbitrary``) axis — the running (m, l, acc) state lives in
VMEM scratch and persists across kv steps, exactly the HBM→VMEM streaming
structure flash attention wants on the MXU.  Block shapes are multiples of
128 on the lane dim; the q/kv tiles are the BlockSpec unit so XLA pipelines
the HBM loads behind the matmuls.

GQA is handled in the index maps (kv head = q head // G) — no materialised
repeat.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale, causal, window, cap, q_offset, kv_valid,
            block_q, block_kv, nk):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # causal / windowed block skipping: a kv block that is entirely masked
    # contributes nothing — skip its matmuls (halves MXU work for causal,
    # makes SWA O(window) instead of masked-O(S))
    needed = jnp.bool_(True)
    if causal:
        first_q = q_offset + i * block_q          # block fully above diagonal
        needed &= j * block_kv <= first_q + block_q - 1
    if window is not None:
        first_q = q_offset + i * block_q          # block fully left of window
        needed &= (j + 1) * block_kv - 1 >= first_q - (window - 1)

    @pl.when(needed)
    def _compute():
        q = q_ref[0]                              # (bq, D)
        k = k_ref[0]                              # (bkv, D)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if cap is not None:
            s = cap * jnp.tanh(s / cap)

        q_pos = q_offset + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0)
        k_pos = j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1)
        mask = jnp.ones((block_q, block_kv), bool)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= (q_pos - k_pos) < window
        if kv_valid is not None:
            mask &= k_pos < kv_valid
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                       # (bq,)
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        # fully-masked rows have m_new == NEG_INF and exp(s-m)=1: mask p
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    q_offset=0, kv_valid=None, scale=None,
                    block_q=128, block_kv=128, interpret=False):
    """q (B, Hq, Sq, D); k, v (B, Hkv, Skv, D) -> (B, Hq, Sq, D)."""
    B, Hq, Sq, D = q.shape
    _, Hkv, Skv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, Sq)
    block_kv = min(block_kv, Skv)
    assert Sq % block_q == 0 and Skv % block_kv == 0, (Sq, Skv)
    nq, nk = Sq // block_q, Skv // block_kv

    qr = q.reshape(B * Hq, Sq, D)
    kr = k.reshape(B * Hkv, Skv, D)
    vr = v.reshape(B * Hkv, Skv, D)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, cap=cap,
        q_offset=q_offset, kv_valid=kv_valid, block_q=block_q,
        block_kv=block_kv, nk=nk)

    out = pl.pallas_call(
        kernel,
        grid=(B * Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_kv, D), lambda h, i, j, G=G: (h // G, j, 0)),
            pl.BlockSpec((1, block_kv, D), lambda h, i, j, G=G: (h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hq, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(B, Hq, Sq, D)
