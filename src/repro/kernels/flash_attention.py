"""Tiled causal GQA flash attention (prefill) — Pallas TPU kernel.

Grid layout: (batch, q_heads, num_q_blocks, num_kv_blocks) with the KV-block
dimension innermost and sequential ("arbitrary"), so the running softmax
statistics (m, l) and the fp32 output accumulator live in VMEM scratch and
carry across KV iterations. Causal blocks above the diagonal are skipped.

VMEM working set per step: q tile (block_q, D) + k/v tiles (block_kv, D) each
in input dtype, plus fp32 scratch (block_q, D) + 2*(block_q, 1). With the
default block_q = block_kv = 512 and D = 128 that is ~0.7 MB — comfortably
inside VMEM — and MXU contractions are (512 x 128 x 512), all multiples of
the 128-lane systolic array.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_KV = 512
NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,      # (1,1,bq,D), (1,1,bk,D), (1,1,bk,D)
    o_ref,                    # (1,1,bq,D)
    acc_ref, m_ref, l_ref,    # scratch: (bq,D) f32, (bq,1) f32, (bq,1) f32
    *,
    causal: bool,
    block_q: int,
    block_kv: int,
    num_kv_blocks: int,
    sm_scale: float,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Skip KV blocks entirely above the causal diagonal.
    if causal:
        run = ik * block_kv <= iq * block_q + block_q - 1
    else:
        run = ik >= 0  # always true, keeps a traced bool

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                   # (bq, bk)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(rows >= cols, s, NEG_INF)
        m_prev = m_ref[...]                            # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                # (bq, 1)
        p = jnp.exp(s - m_new)                         # (bq, bk)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, S, Hq, D)
    k: jax.Array,  # (B, S, Hkv, D)
    v: jax.Array,  # (B, S, Hkv, D)
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, D = q.shape
    Hkv = k.shape[2]
    group = Hq // Hkv
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    nq = S // block_q
    nkv = S // block_kv
    sm_scale = 1.0 / (D ** 0.5)

    qt = q.transpose(0, 2, 1, 3)  # (B, H, S, D)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        num_kv_blocks=nkv,
        sm_scale=sm_scale,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hq, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
            pl.BlockSpec((1, 1, block_kv, D), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # (B, S, Hq, D)
