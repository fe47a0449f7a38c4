"""Mamba2 SSD (state-space duality) chunk scan for TPU (Pallas).

TPU adaptation of the SSD GPU kernel: grid = (B, H, nc) with the chunk axis
last (sequential).  Each grid step computes the intra-chunk quadratic term on
the MXU (an (L,L) masked decay-weighted C·Bᵀ matmul) and advances the
inter-chunk state recurrence — the (P, N) state lives in VMEM scratch across
chunk steps, replacing the GPU version's cross-block shared-memory carry.
No warp-level primitives are needed; the sequential grid + VMEM scratch is
the TPU-idiomatic equivalent (DESIGN.md §2).

Chunk layout requirement: x (B, H, nc, L, P); B/C shared across heads
(n_groups=1): (B, nc, L, N); dt post-softplus (B, H, nc, L); A (H,) < 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, hf_ref,
            state_ref, *, chunk, nc):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0, 0].astype(jnp.float32)        # (L, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)      # (L,)
    A = a_ref[0].astype(jnp.float32)              # scalar
    Bc = b_ref[0, 0].astype(jnp.float32)          # (L, N)
    Cc = c_ref[0, 0].astype(jnp.float32)          # (L, N)
    D = d_ref[0].astype(jnp.float32)

    da = dt * A                                   # (L,)
    cum = jnp.cumsum(da)                          # (L,)
    total = cum[-1]
    xdt = x * dt[:, None]                         # (L, P)

    # intra-chunk: M[t,s] = exp(cum[t]-cum[s]) (C_t·B_s), causal
    CB = jax.lax.dot_general(Cc, Bc, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    seg = cum[:, None] - cum[None, :]
    tri = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    M = jnp.where(tri, jnp.exp(jnp.where(tri, seg, 0.0)) * CB, 0.0)
    y = jax.lax.dot_general(M, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)

    # inter-chunk contribution from carried state: (L,N)@(N,P)
    h_prev = state_ref[...]                       # (N, P)
    y = y + jax.lax.dot_general(Cc * jnp.exp(cum)[:, None], h_prev,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = (y + D * x).astype(y_ref.dtype)

    # state update: h = exp(total) h_prev + sum_s exp(total-cum[s]) B_s ⊗ xdt_s
    w = jnp.exp(total - cum)[:, None]             # (L, 1)
    upd = jax.lax.dot_general(Bc * w, xdt, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (N, P)
    state_ref[...] = jnp.exp(total) * h_prev + upd

    @pl.when(j == nc - 1)
    def _final():
        hf_ref[0, 0] = state_ref[...]


def ssd_scan(x, dt, A, Bm, Cm, D, *, chunk=128, interpret=False):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N), D (H,)
    -> y (B,S,H,P), h_final (B,H,N,P).  S must be a chunk multiple."""
    Bb, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0
    nc = S // chunk
    xc = x.transpose(0, 2, 1, 3).reshape(Bb, H, nc, chunk, P)
    dtc = dt.transpose(0, 2, 1).reshape(Bb, H, nc, chunk)
    Bc = Bm.reshape(Bb, nc, chunk, N)
    Cc = Cm.reshape(Bb, nc, chunk, N)

    kernel = functools.partial(_kernel, chunk=chunk, nc=nc)
    y, hf = pl.pallas_call(
        kernel,
        grid=(Bb, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, j: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b, h, j: (b, h, j, 0)),
            pl.BlockSpec((1,), lambda b, h, j: (h,)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, j: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda b, h, j: (b, j, 0, 0)),
            pl.BlockSpec((1,), lambda b, h, j: (h,)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda b, h, j: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, j: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((Bb, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((Bb, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, dtc, A, Bc, Cc, D)
    y = y.reshape(Bb, H, S, P).transpose(0, 2, 1, 3)
    return y, hf
