"""Level-synchronous browse scoring — Pallas TPU kernel.

One depth level of the batched tree browse (read path): every frontier entry
(query, tree, beam-node) scores its (<= K) packed child embeddings against
that entry's OWN query vector. The host packs the whole batch's frontiers
into a padded (F, K, D) child tensor + (F, D) query tensor; the kernel
computes the masked per-row matvec for a whole block of frontier entries in
one VPU pass — the read-path twin of ``tree_refresh``'s cross-tree batch
dimension.

Grid: (num_frontier_blocks,). Block = (block_f, K, D): with block_f = 64,
K = 8, D = 256 the tile is 512 KB fp32 — VMEM-resident; the reduction axis
is D (lanes stay 128-aligned), K is a small unrolled sublane dim.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK_F = 64


def _browse_kernel(emb_ref, q_ref, mask_ref, out_ref):
    emb = emb_ref[...].astype(jnp.float32)    # (bf, K, D)
    q = q_ref[...].astype(jnp.float32)        # (bf, D)
    m = mask_ref[...].astype(jnp.float32)     # (bf, K)
    s = jnp.sum(emb * q[:, None, :], axis=-1)  # (bf, K)
    out_ref[...] = (s * m).astype(out_ref.dtype)


def browse_scores(
    child_emb: jax.Array,   # (F, K, D) packed frontier children
    q_emb: jax.Array,       # (F, D) per-entry query vector
    child_mask: jax.Array,  # (F, K) 1.0 for real child slots
    *,
    block_f: int = DEFAULT_BLOCK_F,
    interpret: bool = False,
) -> jax.Array:
    F, K, D = child_emb.shape
    block_f = min(block_f, F)
    Fp = -(-F // block_f) * block_f
    if Fp != F:
        child_emb = jnp.pad(child_emb, ((0, Fp - F), (0, 0), (0, 0)))
        q_emb = jnp.pad(q_emb, ((0, Fp - F), (0, 0)))
        child_mask = jnp.pad(child_mask, ((0, Fp - F), (0, 0)))
    mask_f = child_mask.astype(jnp.float32)

    out = pl.pallas_call(
        _browse_kernel,
        grid=(Fp // block_f,),
        in_specs=[
            pl.BlockSpec((block_f, K, D), lambda i: (i, 0, 0)),
            pl.BlockSpec((block_f, D), lambda i: (i, 0)),
            pl.BlockSpec((block_f, K), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_f, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Fp, K), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(child_emb, q_emb, mask_f)
    return out[:F]
