"""Level-parallel MemTree summary refresh — Pallas TPU kernel.

One level of the paper's lazy dirty-path flush (Algorithm 1, lines 10-18):
every dirty parent at a level aggregates its (<= k) children's embeddings
into a normalized interval summary. The host gathers child embeddings into a
padded (P, K, D) tensor (P = dirty parents at this level, K = branching
factor); the kernel computes the masked mean + l2 normalization for a whole
block of parents at once — the paper's thread-pool parallelism becomes one
vectorized VPU pass.

Grid: (num_parent_blocks,). Block = (block_p, K, D): with block_p = 8,
K = 16, D = 256 the tile is 128 KB fp32 — trivially VMEM-resident, and the
reduction axis K is unrolled so the lanes dimension stays D (128-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_P = 8


def _refresh_kernel(emb_ref, mask_ref, out_ref):
    emb = emb_ref[...].astype(jnp.float32)    # (bp, K, D)
    m = mask_ref[...].astype(jnp.float32)     # (bp, K)
    s = jnp.sum(emb * m[..., None], axis=1)   # (bp, D)
    cnt = jnp.maximum(jnp.sum(m, axis=1, keepdims=True), 1.0)
    mean = s / cnt
    norm = jnp.sqrt(jnp.sum(mean * mean, axis=-1, keepdims=True)) + 1e-6
    out_ref[...] = (mean / norm).astype(out_ref.dtype)


def tree_refresh(
    child_emb: jax.Array,   # (P, K, D)
    child_mask: jax.Array,  # (P, K)
    *,
    block_p: int = DEFAULT_BLOCK_P,
    interpret: bool = False,
) -> jax.Array:
    P, K, D = child_emb.shape
    block_p = min(block_p, P)
    Pp = -(-P // block_p) * block_p
    if Pp != P:
        child_emb = jnp.pad(child_emb, ((0, Pp - P), (0, 0), (0, 0)))
        child_mask = jnp.pad(child_mask, ((0, Pp - P), (0, 0)))
    mask_f = child_mask.astype(jnp.float32)

    out = pl.pallas_call(
        _refresh_kernel,
        grid=(Pp // block_p,),
        in_specs=[
            pl.BlockSpec((block_p, K, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_p, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_p, D), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, D), child_emb.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(child_emb, mask_f)
    return out[:P]
