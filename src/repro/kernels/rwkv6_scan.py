"""Chunked RWKV6 (Finch) WKV recurrence — Pallas TPU kernel.

Recurrence (per head; S is the (K, V) state matrix):

    o_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)
    S_t = diag(exp(-exp(w_t))) S_{t-1} + k_tᵀ v_t

The kernel processes the sequence in chunks of C tokens. Within a chunk the
pairwise token interactions are computed directly from per-key cumulative
log-decays (no exp(+cum) factorization — the (C, C, K) log-difference form is
exact and stable because every exponent is <= 0):

    cum[t]   = Σ_{i<=t} -exp(w_i)                      (C, K), decreasing
    A[t,s]   = Σ_k r[t,k] k[s,k] exp(cum[t-1,k]-cum[s,k])   for s < t
    A[t,t]   = Σ_k r[t,k] u[k] k[t,k]
    o        = A @ v + (r ⊙ exp(cum_excl)) @ S_in
    S_out    = exp(cum[C-1]) ⊙ S_in + Σ_s (k_s ⊙ exp(cum[C-1]-cum[s]))ᵀ v_s

Grid: (batch, heads, num_chunks), chunks innermost/sequential; the (K, V)
state lives in fp32 VMEM scratch across chunk iterations. The O(C²K)
intra-chunk tensor is the TPU-native replacement for the GPU kernel's
warp-level recurrence: at C = 64, K = 64 it is a 1 MB fp32 VMEM tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 64


def _rwkv6_kernel(
    r_ref, k_ref, v_ref, w_ref,  # (1,1,C,K) / (1,1,C,V) / (1,1,C,K)
    u_ref,                        # (1, K)
    s0_ref,                       # (1, 1, K, V) initial state
    o_ref,                        # (1, 1, C, V)
    sout_ref,                     # (1, 1, K, V) final state
    state_ref,                    # scratch (K, V) f32
    *,
    chunk: int,
    num_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, 0].astype(jnp.float32)   # (C, K)
    k = k_ref[0, 0].astype(jnp.float32)   # (C, K)
    v = v_ref[0, 0].astype(jnp.float32)   # (C, V)
    w = w_ref[0, 0].astype(jnp.float32)   # (C, K)
    u = u_ref[0].astype(jnp.float32)      # (K,)

    logdec = -jnp.exp(w)                              # (C, K) <= 0
    cum = jnp.cumsum(logdec, axis=0)                  # inclusive, (C, K)
    cum_excl = cum - logdec                           # exclusive (cum[t-1])

    # inter-chunk: contribution of carried state
    r_scaled = r * jnp.exp(cum_excl)                  # (C, K)
    o_inter = jax.lax.dot_general(
        r_scaled, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                                 # (C, V)

    # intra-chunk: exact pairwise log-difference form (all exponents <= 0)
    # diff[t,s,k] = cum_excl[t,k] - cum[s,k]  (valid for s < t)
    diff = cum_excl[:, None, :] - cum[None, :, :]     # (C, C, K)
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    strict = t_idx > s_idx
    gate = jnp.where(strict[..., None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    A = jnp.einsum("tk,sk,tsk->ts", r, k, gate)       # (C, C) strictly lower
    A_diag = jnp.sum(r * u[None, :] * k, axis=1)      # (C,)
    A = A + jnp.where(t_idx == s_idx, A_diag[:, None], 0.0)
    o_intra = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o_ref[0, 0] = (o_inter + o_intra).astype(o_ref.dtype)

    # state update
    total = cum[chunk - 1]                            # (K,)
    k_scaled = k * jnp.exp(total[None, :] - cum)      # (C, K), exponents <= 0
    s_new = jnp.exp(total)[:, None] * state_ref[...] + jax.lax.dot_general(
        k_scaled, v, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )                                                 # (K, V)
    state_ref[...] = s_new

    @pl.when(ic == num_chunks - 1)
    def _finish():
        sout_ref[0, 0] = s_new.astype(sout_ref.dtype)


def rwkv6_scan(
    r: jax.Array,      # (B, T, H, K)
    k: jax.Array,      # (B, T, H, K)
    v: jax.Array,      # (B, T, H, V)
    w: jax.Array,      # (B, T, H, K) raw; decay = exp(-exp(w))
    u: jax.Array,      # (H, K)
    state: jax.Array,  # (B, H, K, V)
    *,
    chunk: int = DEFAULT_CHUNK,
    interpret: bool = False,
):
    B, T, H, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, T)
    assert T % chunk == 0, (T, chunk)
    nc = T // chunk

    # layout: (B, H, T, •)
    rt = r.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    wt = w.transpose(0, 2, 1, 3)
    s0 = state[:, :, None].reshape(B, H, K, V)

    kernel = functools.partial(_rwkv6_kernel, chunk=chunk, num_chunks=nc)
    o, s_final = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, K), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, K), lambda b, h, c: (h, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, V), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, K, V), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, V), r.dtype),
            jax.ShapeDtypeStruct((B, H, K, V), state.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(rt, kt, vt, wt, u, s0)
    return o.transpose(0, 2, 1, 3), s_final
