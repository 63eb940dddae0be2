"""Mesh-sharded dispatch for the serve-path kernels (multi-device serve).

The batch dimensions PRs 1-2 created — the fact-index top-k scan, the
(query, tree) browse-lane frontier, and the cross-tree ``tree_refresh``
flush batch — are embarrassingly parallel. This module places them on a
1-D ``data``-axis mesh (launch/mesh.py) with ``shard_map``:

* ``sharded_topk_sim`` — the fact index rows are sharded; each device runs
  the SAME fused top-k kernel (reference or Pallas) over its local rows,
  then an all-gather of (score, global row) candidates + a two-key sort
  (``topk_sim.merge_topk``) produces the exact global top-k on every
  device. The merge moves S*k candidates, never the (Q, N) score matrix.
* ``sharded_scatter_rows`` / ``upload_sharded`` / ``grow_sharded`` — the
  device-resident index cache's lifecycle under sharding, with per-shard
  row ownership (each shard applies only the updates it owns).
* ``sharded_tree_refresh`` / ``sharded_browse_scores`` — pure data
  parallelism over the parent/frontier dim; per-row math is row-local, so
  results are bitwise identical to the single-device launch.

Row ownership is ROUND-ROBIN: global row g lives on shard ``g % S`` at
local slot ``g // S``. The physical (C, D) array is the shard-major
permutation of the logical matrix (shard 0's strided rows first), sharded
contiguously over the data axis, so each shard's contiguous block IS its
strided row subset. Why round-robin instead of contiguous blocks: capacity
growth appends slots to EVERY shard's local block (a shard-local pad), so
geometric device-cache growth never moves an existing row across devices —
no resharding traffic on the steady-ingest path.

Exactness: per-row scores/normalization/refresh math touch only that row's
values, so sharded results are bitwise identical to single-device; with the
deterministic (score desc, row id asc) tie-break shared by every top-k
path, mesh=None and any mesh size are exactly result-identical.

All builders are cached per (mesh, static shape bucket) so the jit-compile
set stays bounded; meshes are hashable and close over their devices. The
builders that may launch a Pallas kernel turn off shard_map's varying-axes
check (``check_vma=False``): a ``pallas_call`` declares no varying axes.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels import ref as _ref
from repro.kernels.browse_scores import browse_scores as _browse
from repro.kernels.topk_sim import NEG_INF, merge_topk
from repro.kernels.topk_sim import topk_sim as _topk
from repro.kernels.tree_refresh import tree_refresh as _tree_refresh


def mesh_shards(mesh: Optional[Mesh], axis: str = "data") -> int:
    """Data-axis width of ``mesh`` (1 when mesh is None / axis absent)."""
    if mesh is None:
        return 1
    return int(dict(mesh.shape).get(axis, 1))


def pad_rows(n: int, mult: int) -> int:
    """Round ``n`` up to a multiple of ``mult`` (shard-divisible padding)."""
    return -(-n // mult) * mult


def _normalize(x):
    # identical formula to ops.normalize_rows — row-local, so bitwise equal
    # whether applied to the whole matrix or a shard's block
    xf = x.astype(jnp.float32)
    return xf / (jnp.linalg.norm(xf, axis=-1, keepdims=True) + 1e-6)


# ---------------------------------------------------------------------------
# sharded index-cache lifecycle (upload / grow / scatter)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _normalize_sharded(mesh: Mesh, axis: str):
    return jax.jit(jax.shard_map(
        _normalize, mesh=mesh,
        in_specs=P(axis, None), out_specs=P(axis, None)))


def upload_sharded(host: np.ndarray, cap: int, mesh: Mesh, axis: str = "data"):
    """Full upload of a host matrix into the round-robin sharded layout.
    ``cap`` must be a multiple of the mesh's data-axis size; rows beyond the
    host matrix pad with zeros (masked by num_valid downstream)."""
    S = mesh_shards(mesh, axis)
    dim = host.shape[1]
    hp = np.zeros((cap, dim), np.float32)
    hp[: host.shape[0]] = host
    # shard-major permutation: physical row s*(cap//S)+l <- logical row l*S+s
    perm = hp.reshape(cap // S, S, dim).transpose(1, 0, 2).reshape(cap, dim)
    arr = jax.device_put(perm, NamedSharding(mesh, P(axis, None)))
    return _normalize_sharded(mesh, axis)(arr)


def upload_replicated(host: np.ndarray, mesh: Mesh):
    """Full upload of a host matrix replicated across the mesh (the root
    index: small, read by every shard's recall)."""
    arr = jax.device_put(np.ascontiguousarray(host, np.float32),
                         NamedSharding(mesh, P(None, None)))
    return jax.jit(_normalize)(arr)


@functools.lru_cache(maxsize=None)
def _grow_sharded(mesh: Mesh, axis: str, add_per_shard: int):
    def body(a):
        return jnp.pad(a, ((0, add_per_shard), (0, 0)))
    return jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=P(axis, None), out_specs=P(axis, None)))


def grow_sharded(arr, new_cap: int, mesh: Mesh, axis: str = "data"):
    """Geometric device-cache growth under sharding: every shard pads its
    local block — existing rows keep their owner, nothing crosses devices."""
    S = mesh_shards(mesh, axis)
    add = (new_cap - arr.shape[0]) // S
    return _grow_sharded(mesh, axis, add)(arr)


@functools.lru_cache(maxsize=None)
def _scatter_sharded(mesh: Mesh, axis: str):
    S = mesh_shards(mesh, axis)

    def body(a, idx, rows):
        s = jax.lax.axis_index(axis)
        # per-shard row ownership: this shard applies only the updates for
        # rows it owns; everything else (and -1 padding) drops out of bounds
        mine = (idx >= 0) & (idx % S == s.astype(idx.dtype))
        li = jnp.where(mine, idx // S, a.shape[0])
        return a.at[li].set(_normalize(rows), mode="drop")

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None), P(None), P(None, None)),
        out_specs=P(axis, None)))


def sharded_scatter_rows(arr, idx, rows, *, mesh: Mesh, axis: str = "data"):
    """Incremental sharded-index update: normalized ``rows`` land at global
    row ids ``idx`` (int32; -1 entries are padding and dropped)."""
    return _scatter_sharded(mesh, axis)(arr, jnp.asarray(idx, jnp.int32),
                                        jnp.asarray(rows))


# ---------------------------------------------------------------------------
# sharded fused top-k scan
# ---------------------------------------------------------------------------
def _local_topk(q, kk, k, num_valid, impl):
    if impl == "reference":
        return _ref.topk_sim_ref(q, kk, k, normalize=False,
                                 num_valid=num_valid)
    return _topk(q, kk, k, normalize=False, num_valid=num_valid,
                 interpret=(impl == "pallas_interpret"))


@functools.lru_cache(maxsize=None)
def _topk_sharded(mesh: Mesh, axis: str, k: int, k_local: int, impl: str):
    S = mesh_shards(mesh, axis)

    def body(nv, q, kk):
        s = jax.lax.axis_index(axis).astype(jnp.int32)
        # valid rows this shard owns: #{g < nv : g % S == s}
        local_nv = jnp.maximum((nv - s + S - 1) // S, 0)
        vals, idx = _local_topk(q, kk, k_local, local_nv, impl)
        gidx = jnp.where(idx >= 0, idx * S + s, -1)
        vals = jnp.where(idx >= 0, vals, NEG_INF)
        av = jax.lax.all_gather(vals, axis)            # (S, Q, k_local)
        ai = jax.lax.all_gather(gidx, axis)
        pool_v = jnp.moveaxis(av, 0, 1).reshape(q.shape[0], S * k_local)
        pool_i = jnp.moveaxis(ai, 0, 1).reshape(q.shape[0], S * k_local)
        return merge_topk(pool_v, pool_i, k)

    fn = jax.shard_map(body, mesh=mesh,
                   in_specs=(P(), P(None, None), P(axis, None)),
                   out_specs=(P(None, None), P(None, None)),
                   check_vma=False)
    return jax.jit(fn)


def sharded_topk_sim(queries, keys, k: int, *, mesh: Mesh, axis: str = "data",
                     num_valid=None, impl: str = "reference"):
    """Fused top-k over a round-robin sharded key matrix: shard-local top-k
    + cross-device candidate merge. ``queries`` must be pre-normalized (the
    sharded cache stores normalized rows); returns (vals, idx) with GLOBAL
    row indices, exactly equal to the single-device ``topk_sim`` result."""
    S = mesh_shards(mesh, axis)
    shard_rows = keys.shape[0] // S
    k_local = min(k, shard_rows)
    nv = jnp.asarray(keys.shape[0] if num_valid is None else num_valid,
                     jnp.int32)
    return _topk_sharded(mesh, axis, k, k_local, impl)(nv, queries, keys)


@functools.lru_cache(maxsize=None)
def _topk_replicated(mesh: Mesh, k: int, impl: str):
    def body(nv, q, kk):
        return _local_topk(q, kk, k, nv, impl)

    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P(), P()),
                                 out_specs=(P(), P()), check_vma=False))


def replicated_topk_sim(queries, keys, k: int, *, mesh: Mesh,
                        num_valid=None, impl: str = "reference"):
    """Fused top-k over a key matrix replicated on every device of ``mesh``
    (the root index). Every device scans its own copy inside ``shard_map``
    — a Pallas kernel cannot be partitioned automatically — and the result
    is the single-device ``topk_sim`` result, replicated."""
    nv = jnp.asarray(keys.shape[0] if num_valid is None else num_valid,
                     jnp.int32)
    return _topk_replicated(mesh, k, impl)(nv, queries, keys)


# ---------------------------------------------------------------------------
# sharded flush / browse batches (pure data parallelism over the batch dim)
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=None)
def _tree_refresh_sharded(mesh: Mesh, axis: str, impl: str):
    def body(emb, mask):
        if impl == "reference":
            return _ref.tree_refresh_ref(emb, mask)
        return _tree_refresh(emb, mask,
                             interpret=(impl == "pallas_interpret"))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None)),
        out_specs=P(axis, None), check_vma=False))


def sharded_tree_refresh(child_emb, child_mask, *, mesh: Mesh,
                         axis: str = "data", impl: str = "reference"):
    """One flush level's (P, K, D) cross-tree refresh batch, parents sharded
    over the mesh. P must be a multiple of the data-axis size (the Forest
    pads its power-of-two bucket up to a shard multiple)."""
    return _tree_refresh_sharded(mesh, axis, impl)(
        jnp.asarray(child_emb), jnp.asarray(child_mask))


@functools.lru_cache(maxsize=None)
def _browse_sharded(mesh: Mesh, axis: str, impl: str):
    def body(emb, q, mask):
        if impl == "reference":
            return _ref.browse_scores_ref(emb, q, mask)
        return _browse(emb, q, mask, interpret=(impl == "pallas_interpret"))

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(axis, None, None), P(axis, None), P(axis, None)),
        out_specs=P(axis, None), check_vma=False))


def sharded_browse_scores(child_emb, q_emb, child_mask, *, mesh: Mesh,
                          axis: str = "data", impl: str = "reference"):
    """One browse depth level's packed (F, K, D) frontier, lanes sharded
    over the mesh. F must be a multiple of the data-axis size (the
    Retriever pads its power-of-two bucket up to a shard multiple)."""
    return _browse_sharded(mesh, axis, impl)(
        jnp.asarray(child_emb), jnp.asarray(q_emb), jnp.asarray(child_mask))
