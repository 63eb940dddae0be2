"""Chunked Mamba2 SSD scan — Pallas TPU kernel.

Recurrence (per head; S is the (P, N) state; scalar decay per head):

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t ⊗ B_t
    y_t = S_t C_t

Because the decay is a *scalar* per head (Mamba2's SSD restriction), the
chunked factorization is unconditionally stable: with cumulative log-decay
cum[t] = Σ_{i<=t} dt_i A_h (A_h < 0 so cum is decreasing),

    y_intra[t] = Σ_{s<=t} exp(cum[t]-cum[s]) dt_s (C_t·B_s) x_s
    y_inter[t] = exp(cum[t]) (S_in C_t)
    S_out      = exp(cum[C-1]) S_in + Σ_s exp(cum[C-1]-cum[s]) dt_s x_s ⊗ B_s

and every exponent is <= 0. The intra-chunk term is two MXU matmuls:
G = (C Bᵀ) ⊙ decay-mask (C x C), then G @ x.

Grid: (batch, heads, num_chunks), chunks innermost/sequential, (P, N) fp32
state in VMEM scratch. B/C are shared across heads (single SSD group).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 64


def _ssd_kernel(
    x_ref,    # (1, 1, C, P)
    dt_ref,   # (1, 1, C, 1)
    a_ref,    # (1, 1) — A_h (negative scalar)
    b_ref,    # (1, C, N)
    c_ref,    # (1, C, N)
    s0_ref,   # (1, 1, P, N)
    y_ref,    # (1, 1, C, P)
    sout_ref, # (1, 1, P, N)
    state_ref,  # scratch (P, N) f32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)     # (C, P)
    dt = dt_ref[0, 0].astype(jnp.float32)   # (C, 1)
    A = a_ref[0, 0].astype(jnp.float32)     # scalar
    Bm = b_ref[0].astype(jnp.float32)       # (C, N)
    Cm = c_ref[0].astype(jnp.float32)       # (C, N)

    cum = jnp.cumsum(dt * A, axis=0)        # (C, 1), decreasing
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # decay[t,s] = exp(cum[t]-cum[s]) for s <= t else 0
    dmat = jnp.where(
        t_idx >= s_idx,
        jnp.exp(jnp.minimum(cum - cum.T, 0.0)),
        0.0,
    )                                        # (C, C)
    cb = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # (C, C): C_t · B_s
    G = cb * dmat * dt.T                     # (C, C) — includes dt_s
    y_intra = jax.lax.dot_general(
        G, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # (C, P)
    # inter: y_inter[t] = exp(cum[t]) * C_t @ S_inᵀ  -> (C, P)
    y_inter = jnp.exp(cum) * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    total = cum[chunk - 1]                   # (1,)
    xw = x * (dt * jnp.exp(jnp.minimum(total[None, :] - cum, 0.0)))  # (C, P)
    s_new = jnp.exp(total)[:, None] * state_ref[...] + jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                        # (P, N)
    state_ref[...] = s_new

    @pl.when(ic == num_chunks - 1)
    def _finish():
        sout_ref[0, 0] = s_new.astype(sout_ref.dtype)


def mamba2_ssd(
    x: jax.Array,      # (B, T, H, P)
    dt: jax.Array,     # (B, T, H) — softplus'd, > 0
    A: jax.Array,      # (H,) — negative
    Bm: jax.Array,     # (B, T, N)
    C: jax.Array,      # (B, T, N)
    state: jax.Array,  # (B, H, P, N)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
):
    B, T, H, P = x.shape
    N = Bm.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    xt = x.transpose(0, 2, 1, 3)                    # (B, H, T, P)
    dtt = dt.transpose(0, 2, 1)[..., None]          # (B, H, T, 1)
    a2 = A.reshape(H, 1).astype(jnp.float32)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, num_chunks=nc)
    y, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, N), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, P), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, P, N), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xt, dtt, a2, Bm, C, state)
    return y.transpose(0, 2, 1, 3), s_final
