"""Single-token GQA decode attention over a KV cache — Pallas TPU kernel.

Decode is memory-bound: the kernel streams the KV cache through VMEM in
(block_kv, D) tiles while the q tile for one whole GQA group (all query heads
sharing a KV head) stays resident. Grid: (batch, kv_heads, num_kv_blocks),
KV innermost/sequential with fp32 online-softmax scratch.

Variable cache lengths arrive by scalar prefetch (SMEM, one int32 per
sequence); slots at or beyond the length are masked, and KV blocks wholly
beyond it are skipped. The cache layout is (B, S, Hkv, D) — the
same layout `models.transformer` maintains — transposed to (B, Hkv, S, D)
outside the kernel so tiles are contiguous along the streamed axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_KV = 1024
NEG_INF = -1e30


def _decode_kernel(
    len_ref,                  # (B,) int32 scalar prefetch
    q_ref,                    # (1, 1, G, D)
    k_ref, v_ref,             # (1, 1, bk, D)
    o_ref,                    # (1, 1, G, D)
    acc_ref, m_ref, l_ref,    # scratch: (G, D) f32, (G, 1) f32, (G, 1) f32
    *,
    block_kv: int,
    num_kv_blocks: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    length = len_ref[b]
    # Skip blocks entirely beyond the valid cache length.
    @pl.when(ik * block_kv < length)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)        # (G, D)
        k = k_ref[0, 0].astype(jnp.float32)        # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)        # (bk, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * sm_scale                                # (G, bk)
        pos = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(pos < length, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,        # (B, Hq, D) — one new token per sequence
    k_cache: jax.Array,  # (B, Smax, Hkv, D)
    v_cache: jax.Array,  # (B, Smax, Hkv, D)
    lengths: jax.Array,  # (B,) int32
    *,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
) -> jax.Array:
    B, Smax, Hkv, D = k_cache.shape
    Hq = q.shape[1]
    group = Hq // Hkv
    block_kv = min(block_kv, Smax)
    assert Smax % block_kv == 0, (Smax, block_kv)
    nkv = Smax // block_kv
    sm_scale = 1.0 / (D ** 0.5)

    qg = q.reshape(B, Hkv, group, D)
    kt = k_cache.transpose(0, 2, 1, 3)  # (B, Hkv, S, D)
    vt = v_cache.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _decode_kernel,
        block_kv=block_kv,
        num_kv_blocks=nkv,
        sm_scale=sm_scale,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, Hkv, nkv),
            in_specs=[
                pl.BlockSpec((1, 1, group, D), lambda b, h, ik, ln: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik, ln: (b, h, ik, 0)),
                pl.BlockSpec((1, 1, block_kv, D), lambda b, h, ik, ln: (b, h, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, group, D), lambda b, h, ik, ln: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, D), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
                pltpu.VMEM((group, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, kt, vt)
    return out.reshape(B, Hq, D)
