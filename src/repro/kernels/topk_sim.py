"""Fused cosine-similarity + top-k — Pallas TPU kernel.

This is MemForest's retrieval hot path: forest recall scores a query against
all tree-root embeddings; fact-to-tree recall scores it against the canonical
fact index. Fusing normalize + matmul + running top-k selection avoids ever
materializing the full (Q, N) score matrix in HBM — the kernel streams key
tiles through VMEM and keeps a (block_q, K) running top-k in scratch.

Grid: (num_q_blocks, num_key_blocks), key blocks innermost/sequential.
Selection: per key tile, the candidate pool is the running top-k (block_q, K)
plus the tile scores (block_q, block_kv); K rounds of max + masked-min-index
+ mask extract the new top-k. K <= 32 keeps this cheap relative to the
(block_q x D x block_kv) MXU matmul.

Tie-break contract: results are ordered by (score desc, key index asc). Each
selection round picks the smallest global key index among the entries that
hold the round's max score, so the order holds by construction. The
reference oracle and the cross-shard candidate merge (:func:`merge_topk`)
implement the same order explicitly, so single-device and mesh-sharded
retrieval are exactly result-identical, not tie-lucky.

The valid-key count arrives by scalar prefetch (SMEM); scores use f32 at
HIGHEST precision, as the oracle does, so both agree to f32 rounding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_KV = 512
# Bytes of one f32 key tile. At D=3072 a 512-row tile is 6 MiB, 12 MiB
# double-buffered, close to the default scoped-VMEM limit; the tile height
# shrinks (in steps of 128 rows) so that wide embeddings stay well inside it.
_KEY_TILE_BYTES = 4 << 20
NEG_INF = -1e30


def _topk_kernel(
    nv_ref,                # (1,) int32 scalar prefetch — number of valid keys
    q_ref,                 # (bq, D) — pre-normalized
    k_ref,                 # (bk, D) — pre-normalized
    vals_ref, idx_ref,     # (bq, K) f32 / int32 outputs
    tv_ref, ti_ref,        # scratch: (bq, K) f32 / int32 running top-k
    *,
    k: int,
    block_kv: int,
    num_kv_blocks: int,
):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        tv_ref[...] = jnp.full_like(tv_ref, NEG_INF)
        ti_ref[...] = jnp.full_like(ti_ref, -1)

    q = q_ref[...].astype(jnp.float32)
    kk = k_ref[...].astype(jnp.float32)
    scores = jax.lax.dot_general(
        q, kk, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )  # (bq, bk)
    cols = ik * block_kv + jax.lax.broadcasted_iota(jnp.int32, scores.shape, 1)
    scores = jnp.where(cols < nv_ref[0], scores, NEG_INF)  # mask padded keys

    # The candidate pool is the running top-k plus this tile, kept as two
    # arrays (a lane-unaligned concatenate does not lower on TPU). Each round
    # takes the pool's max score and, among the entries holding it, the
    # smallest key index — the (score desc, index asc) order by construction,
    # with no gather. Global indices are unique, so masking by index removes
    # exactly the selected entry (dead -1 slots are all NEG_INF anyway).
    run_v, run_i = tv_ref[...], ti_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, run_v.shape, 1)
    out_v = jnp.full_like(run_v, NEG_INF)
    out_i = jnp.full_like(run_i, -1)
    big = jnp.iinfo(jnp.int32).max
    for j in range(k):
        m = jnp.maximum(jnp.max(run_v, axis=1, keepdims=True),
                        jnp.max(scores, axis=1, keepdims=True))      # (bq, 1)
        sel = jnp.minimum(
            jnp.min(jnp.where(run_v == m, run_i, big), axis=1, keepdims=True),
            jnp.min(jnp.where(scores == m, cols, big), axis=1, keepdims=True))
        out_v = jnp.where(slot == j, m, out_v)
        out_i = jnp.where(slot == j, sel, out_i)
        run_v = jnp.where(run_i == sel, NEG_INF, run_v)
        scores = jnp.where(cols == sel, NEG_INF, scores)
    tv_ref[...] = out_v
    ti_ref[...] = out_i

    @pl.when(ik == num_kv_blocks - 1)
    def _finish():
        vals_ref[...] = out_v
        idx_ref[...] = jnp.where(out_v > NEG_INF / 2, out_i, -1)


def merge_topk(vals: jax.Array, idx: jax.Array, k: int):
    """Deterministic top-k over a candidate pool: (Q, C) scores + global key
    indices -> (Q, k) ordered by (score desc, index asc). Dead candidates
    carry vals == NEG_INF / idx == -1 and sort last; surviving dead slots are
    re-masked to idx -1 (matches the kernel/oracle contract).

    This is the cross-device reduction of the mesh-sharded scan
    (kernels/shard_ops.py): each shard contributes its local top-k as
    (score, global row) candidates and the merge is a cheap (Q, S*k)
    two-key sort — never the full (Q, N) score matrix."""
    neg = -vals
    sneg, sidx = jax.lax.sort((neg, idx), dimension=-1, num_keys=2)
    out_v = -sneg[..., :k]
    out_i = sidx[..., :k]
    return out_v, jnp.where(out_v > NEG_INF / 2, out_i, -1)


def _pad_to(x: jax.Array, n: int, axis: int = 0) -> jax.Array:
    pad = n - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def topk_sim(
    queries: jax.Array,  # (Q, D)
    keys: jax.Array,     # (N, D)
    k: int,
    *,
    normalize: bool = True,
    num_valid=None,      # optional traced scalar (defaults to N)
    block_q: int = DEFAULT_BLOCK_Q,
    block_kv: int = DEFAULT_BLOCK_KV,
    interpret: bool = False,
):
    Q, D = queries.shape
    N = keys.shape[0]
    qf = queries.astype(jnp.float32)
    kf = keys.astype(jnp.float32)
    if normalize:
        qf = qf / (jnp.linalg.norm(qf, axis=-1, keepdims=True) + 1e-6)
        kf = kf / (jnp.linalg.norm(kf, axis=-1, keepdims=True) + 1e-6)

    block_q = min(block_q, max(Q, 8))
    fit = max(128, (_KEY_TILE_BYTES // (4 * D)) // 128 * 128)
    block_kv = min(block_kv, fit, max(N, 8))
    Qp = -(-Q // block_q) * block_q
    Np = -(-N // block_kv) * block_kv
    qp = _pad_to(qf, Qp)
    kp = _pad_to(kf, Np)
    nq = Qp // block_q
    nkv = Np // block_kv
    nv = jnp.asarray(N if num_valid is None else num_valid, jnp.int32).reshape(1)

    kernel = functools.partial(
        _topk_kernel,
        k=k,
        block_kv=block_kv,
        num_kv_blocks=nkv,
    )
    vals, idx = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(nq, nkv),
            in_specs=[
                pl.BlockSpec((block_q, D), lambda iq, ik, nv: (iq, 0)),
                pl.BlockSpec((block_kv, D), lambda iq, ik, nv: (ik, 0)),
            ],
            out_specs=[
                pl.BlockSpec((block_q, k), lambda iq, ik, nv: (iq, 0)),
                pl.BlockSpec((block_q, k), lambda iq, ik, nv: (iq, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, k), jnp.float32),
                pltpu.VMEM((block_q, k), jnp.int32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Qp, k), jnp.float32),
            jax.ShapeDtypeStruct((Qp, k), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(nv, qp, kp)
    return vals[:Q], idx[:Q]
