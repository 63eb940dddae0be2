"""Causal GQA flash attention (prefill) — Pallas TPU kernels.

``flash_attention`` picks one of two paths from the input shape.

Packed path, for a sequence that fits one tile (``S <= block_q`` and
``S <= block_kv``): one grid step handles G (row, kv-head) slabs. The
``g = Hq / Hkv`` query heads of one kv head lie along the rows of its q
slab, so q is (B*Hkv, g*S, D) against k and v (B*Hkv, S, D), and each slab
is one batched contraction over the leading dimension; query row r holds
position ``r mod S``, which is what the causal mask compares. One kv block
covers the sequence, so the softmax is one pass with no running statistics
and no kv grid axis. G (``slabs_per_step``) is the largest power of two
that divides B*Hkv, puts at most ``PACKED_ROWS`` query rows in a step, and
keeps ``packed_vmem_bytes`` (double-buffered tiles plus f32 temporaries)
under ``VMEM_BUDGET``; where even one slab does not fit, the tiled path
runs. An encoder forward of 256 rows (widths 16-128, 32 heads of 96,
bf16) thus takes 64-512 steps a layer in place of 8192, one per (row, head).

Tiled path, otherwise: grid (batch, q_heads, num_q_blocks, num_kv_blocks)
with the kv-block dimension innermost and sequential ("arbitrary"), so the
running softmax statistics (m, l) and the fp32 output accumulator live in
VMEM scratch and carry across kv iterations. Causal blocks above the
diagonal are skipped. With block_q = block_kv = 512 and D = 128 a step
holds ~0.7 MB, and the contractions are (512 x 128 x 512).

Both paths upcast q, k and v to fp32 and keep the softmax and the
accumulation in fp32; the output has q's dtype. v may be narrower than q
and k (latent attention: q/k 192 wide, v 128); the softmax scale defaults
to 1/sqrt(q/k width).
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
PACKED_ROWS = 2048              # query rows a packed step aims for
VMEM_BUDGET = 12 * 1024 * 1024  # of 16 MiB scoped VMEM; room for what the estimate misses


def _tile_bytes(lead: int, rows: int, cols: int, itemsize: int) -> int:
    """VMEM bytes of a (lead, rows, cols) array, padded to the (sublane,
    128-lane) tile of its dtype."""
    sub = 8 * max(1, 4 // itemsize)
    return lead * -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def packed_vmem_bytes(slabs: int, group: int, seq: int, head_dim: int,
                      itemsize: int, v_dim: int = 0) -> int:
    """VMEM a packed step of ``slabs`` slabs holds: the q, k, v and output
    tiles double-buffered in the input dtype, and the fp32 temporaries
    (upcast q, k, v, scores, probabilities, output). v and the output are
    ``v_dim`` wide (0: ``head_dim``)."""
    rows, dv = group * seq, v_dim or head_dim
    tiles = (_tile_bytes(slabs, rows, head_dim, itemsize)
             + _tile_bytes(slabs, rows, dv, itemsize)
             + _tile_bytes(slabs, seq, head_dim, itemsize)
             + _tile_bytes(slabs, seq, dv, itemsize))
    temps = (_tile_bytes(slabs, rows, head_dim, 4) + _tile_bytes(slabs, rows, dv, 4)
             + _tile_bytes(slabs, seq, head_dim, 4) + _tile_bytes(slabs, seq, dv, 4)
             + 2 * _tile_bytes(slabs, rows, seq, 4))
    return 2 * tiles + temps


def slabs_per_step(n_slabs: int, group: int, seq: int, head_dim: int,
                   itemsize: int, v_dim: int = 0) -> int:
    """G for the packed path: the largest power of two that divides
    ``n_slabs`` (= B*Hkv), holds at most ``PACKED_ROWS`` query rows (at
    least one slab) and fits ``VMEM_BUDGET``; 0 where one slab does not fit."""
    fits = lambda n: packed_vmem_bytes(n, group, seq, head_dim, itemsize,
                                       v_dim) <= VMEM_BUDGET
    slabs = 1
    while (n_slabs % (2 * slabs) == 0 and 2 * slabs * group * seq <= PACKED_ROWS
           and fits(2 * slabs)):
        slabs *= 2
    return slabs if fits(slabs) else 0


def _packed_kernel(q_ref, k_ref, v_ref, o_ref, *, causal: bool, seq: int,
                   sm_scale: float):
    # q_ref: (G, g*S, D); k_ref: (G, S, D); v_ref: (G, S, Dv); o_ref: (G, g*S, Dv)
    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((2,), (2,)), ((0,), (0,))), preferred_element_type=jnp.float32
    ) * sm_scale                                       # (G, g*S, S)
    if causal:
        rows = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 0) % seq
        cols = jax.lax.broadcasted_iota(jnp.int32, s.shape[1:], 1)
        s = jnp.where((rows >= cols)[None], s, NEG_INF)
    m = jnp.max(s, axis=2, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=2, keepdims=True), 1e-30)
    o = jax.lax.dot_general(
        p, v, (((2,), (1,)), ((0,), (0,))), preferred_element_type=jnp.float32
    )                                                  # (G, g*S, Dv)
    o_ref[...] = (o / l).astype(o_ref.dtype)


def _packed_attention(q, k, v, *, causal: bool, slabs: int, sm_scale: float,
                      interpret: bool):
    B, S, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    g = Hq // Hkv
    n = B * Hkv
    qp = q.reshape(B, S, Hkv, g, D).transpose(0, 2, 3, 1, 4).reshape(n, g * S, D)
    kp = k.transpose(0, 2, 1, 3).reshape(n, S, D)
    vp = v.transpose(0, 2, 1, 3).reshape(n, S, Dv)
    kernel = functools.partial(_packed_kernel, causal=causal, seq=S,
                               sm_scale=sm_scale)
    q_spec = pl.BlockSpec((slabs, g * S, D), lambda i: (i, 0, 0))
    k_spec = pl.BlockSpec((slabs, S, D), lambda i: (i, 0, 0))
    v_spec = pl.BlockSpec((slabs, S, Dv), lambda i: (i, 0, 0))
    o_spec = pl.BlockSpec((slabs, g * S, Dv), lambda i: (i, 0, 0))
    out = pl.pallas_call(
        kernel,
        grid=(n // slabs,),
        in_specs=[q_spec, k_spec, v_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((n, g * S, Dv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(B, Hkv, g, S, Dv).transpose(0, 3, 1, 2, 4).reshape(B, S, Hq, Dv)


def _flash_kernel(
    q_ref, k_ref, v_ref,      # (1,1,bq,D), (1,1,bk,D), (1,1,bk,Dv)
    o_ref,                    # (1,1,bq,Dv)
    acc_ref, m_ref, l_ref,    # scratch: (bq,Dv) f32, (bq,1) f32, (bq,1) f32
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
        v = v_ref[0, 0].astype(jnp.float32)           # (bk, Dv)
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
    v: jax.Array,  # (B, S, Hkv, Dv)
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    sm_scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    B, S, Hq, D = q.shape
    Hkv, Dv = k.shape[2], v.shape[3]
    group = Hq // Hkv
    if sm_scale is None:
        sm_scale = 1.0 / (D ** 0.5)
    if S <= block_q and S <= block_kv:
        slabs = slabs_per_step(B * Hkv, group, S, D, q.dtype.itemsize, Dv)
        if slabs:
            return _packed_attention(q, k, v, causal=causal, slabs=slabs,
                                     sm_scale=sm_scale, interpret=interpret)
    block_q = min(block_q, S)
    block_kv = min(block_kv, S)
    assert S % block_q == 0 and S % block_kv == 0, (S, block_q, block_kv)
    nq = S // block_q
    nkv = S // block_kv

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
            pl.BlockSpec((1, 1, block_kv, Dv), lambda b, h, iq, ik: (b, h // group, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # (B, S, Hq, D)
