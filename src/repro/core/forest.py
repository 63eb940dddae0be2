"""Forest: the shared memory substrate (paper §3.1) + batched lazy refresh
(Algorithm 1).

Persistent state (source of truth): canonical facts, dialogue cells, scope
assignments, MemTree structure, placement maps, session registry.
Derived artifacts: interval summaries, node embeddings, root-index rows,
fact-index rows — regenerated selectively from dirty paths.

`flush()` is Algorithm 1 lines 9-22: dirty nodes are collected by level
across ALL dirty trees, and each level is refreshed in ONE batched
`tree_refresh` kernel call — the paper's same-level/cross-tree parallelism
mapped onto the TPU batch dimension. The dependent depth is the max dirty
level (= deepest affected tree path), not the number of touched paths.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Set, Tuple

import jax.numpy as jnp
import numpy as np

from repro.config import MemForestConfig
from repro.core.memtree import TreeArena
from repro.core.types import CanonicalFact, DialogueCell
from repro.kernels import ops, shard_ops
from repro.obs import Observability, get_obs


class Forest:
    def __init__(self, config: MemForestConfig,
                 kernel_impl: Optional[str] = None,
                 obs: Optional[Observability] = None):
        self.config = config
        # None: Pallas on TPU, the reference elsewhere (ops.resolve_impl)
        self.kernel_impl = ops.resolve_impl(kernel_impl)
        self.obs = get_obs(obs)
        self.trees: Dict[str, TreeArena] = {}
        self._tree_order: List[str] = []          # tree_id -> scope_key
        self.facts: List[CanonicalFact] = []
        self.fact_emb = np.zeros((0, config.embed_dim), np.float32)
        self.fact_alive: List[bool] = []
        self.cells: List[DialogueCell] = []
        # placement: ("fact"|"cell", item_id) -> [(scope_key, node_id)]
        self.placement: Dict[Tuple[str, int], List[Tuple[str, int]]] = {}
        self.session_registry: Dict[str, Dict[str, List[int]]] = {}
        # exactly-once bookkeeping: idempotency keys of applied lifecycle
        # ops (journaled ingest/delete/merge). Persisted in snapshots, so a
        # snapshot + journal-tail replay never double-applies an op.
        self.applied_ops: Set[str] = set()
        # scene clustering state
        self.scene_centroids = np.zeros((0, config.embed_dim), np.float32)
        self.scene_counts: List[int] = []
        self.dirty_trees: Set[str] = set()
        # derived: root index
        self._root_matrix = np.zeros((0, config.embed_dim), np.float32)
        # device-resident L2-normalized index caches (read path): the fact
        # and root matrices live on device between queries, invalidated
        # incrementally — appends sync [synced, n), in-place edits land in a
        # dirty-row set, capacity growth grows the device buffer in place
        # (geometric, no re-upload). topk_sim then runs with normalize=False:
        # no per-query host->device transfer and no O(N*D) re-normalization.
        self._fact_dev = None
        self._fact_dev_rows = 0
        self._fact_dev_dirty: Set[int] = set()
        self._root_dev = None
        self._root_dev_rows = 0
        self._root_dev_dirty: Set[int] = set()
        # multi-device serve: when a mesh is attached (set_mesh), the fact
        # index cache is row-sharded round-robin over the mesh's data axis
        # and read through kernels/shard_ops; the root index is replicated.
        # mesh=None is the single-device fast path (byte-identical to the
        # pre-mesh code).
        self.mesh = None
        self.mesh_axis = "data"
        # counters (benchmarks read these)
        self.summary_refreshes = 0
        self.flush_levels = 0
        self.flush_calls = 0
        self.index_uploads = 0          # full device (re-)uploads
        self.index_row_updates = 0      # incremental scatter updates
        self.index_grows = 0            # device-side capacity grows
        self.index_releases = 0         # device-cache frees (demotion)

    # ------------------------------------------------------------------
    # persistent-state writes
    # ------------------------------------------------------------------
    def get_tree(self, scope_key: str, kind: str) -> TreeArena:
        t = self.trees.get(scope_key)
        if t is None:
            t = TreeArena(len(self._tree_order), scope_key, kind,
                          self.config.branching_factor, self.config.embed_dim)
            self.trees[scope_key] = t
            self._tree_order.append(scope_key)
            if len(self._tree_order) > self._root_matrix.shape[0]:
                grow = max(8, self._root_matrix.shape[0])
                self._root_matrix = np.concatenate(
                    [self._root_matrix, np.zeros((grow, self.config.embed_dim), np.float32)]
                )
                # capacity growth: _sync_device grows the device buffer in
                # place (no full re-upload)
        return t

    def add_fact(self, fact: CanonicalFact) -> int:
        fact.fact_id = len(self.facts)
        self.facts.append(fact)
        self.fact_alive.append(True)
        if fact.fact_id >= self.fact_emb.shape[0]:
            grow = max(64, self.fact_emb.shape[0])
            self.fact_emb = np.concatenate(
                [self.fact_emb, np.zeros((grow, self.config.embed_dim), np.float32)]
            )
            # capacity growth: device buffer grows in place at next sync
        self.fact_emb[fact.fact_id] = fact.emb
        sid = fact.sources[0][0] if fact.sources else ""
        self.session_registry.setdefault(sid, {"facts": [], "cells": []})["facts"].append(fact.fact_id)
        return fact.fact_id

    def kill_fact(self, fact_id: int) -> None:
        """Mark a fact dead and inert its index row (host + device)."""
        self.fact_alive[fact_id] = False
        self.fact_emb[fact_id] = 0.0
        self._fact_dev_dirty.add(fact_id)

    def add_cell(self, cell: DialogueCell) -> int:
        cell.cell_id = len(self.cells)
        self.cells.append(cell)
        self.session_registry.setdefault(cell.session_id, {"facts": [], "cells": []})["cells"].append(cell.cell_id)
        return cell.cell_id

    def insert_item(self, scope_key: str, kind: str, item_kind: str,
                    item_id: int, ts: float, emb: np.ndarray, text: str) -> int:
        tree = self.get_tree(scope_key, kind)
        leaf = tree.insert_leaf(item_id if item_kind == "fact" else -item_id - 1, ts, emb, text)
        self.placement.setdefault((item_kind, item_id), []).append((scope_key, leaf))
        self.dirty_trees.add(scope_key)
        return leaf

    # ------------------------------------------------------------------
    # lazy refresh (Algorithm 1) — level-parallel, batched across trees
    # ------------------------------------------------------------------
    def flush(self, *, level_parallel: Optional[bool] = None,
              only: Optional[Set[str]] = None) -> Dict[str, int]:
        """Refresh all dirty derived artifacts. Returns counters for this
        flush: {"refreshes": distinct dirty nodes, "levels": dependent depth,
        "kernel_calls": batched refresh invocations}.

        ``only`` restricts the flush to a subset of the dirty trees — the
        maintenance plane uses this to drain refresh work in bounded chunks
        between serve steps. Because dirty paths never cross trees, flushing
        the dirty set in any chunking yields the same final derived state as
        one full flush."""
        if level_parallel is None:
            level_parallel = self.config.level_parallel
        self.flush_calls += 1
        targets = set(self.dirty_trees) if only is None else \
            self.dirty_trees & set(only)
        with self.obs.span("forest.flush", trees=len(targets)) as sp:
            out = self._flush(level_parallel, targets)
            sp.set(refreshes=out["refreshes"], levels=out["levels"],
                   kernel_calls=out["kernel_calls"])
        return out

    def _flush(self, level_parallel: bool, targets: Set[str]) -> Dict[str, int]:
        K = self.config.branching_factor
        dim = self.config.embed_dim
        per_tree = {tid: self.trees[tid].dirty_by_level() for tid in targets}
        max_level = 0
        refreshes = 0
        kernel_calls = 0
        for levels in per_tree.values():
            for lam in levels:
                max_level = max(max_level, lam)

        for lam in range(1, max_level + 1):
            batch: List[Tuple[TreeArena, int]] = []
            for tid, levels in per_tree.items():
                tree = self.trees[tid]
                for n in levels.get(lam, []):
                    batch.append((tree, n))
            if not batch:
                continue
            if level_parallel:
                kernel_calls += self._refresh_batch(batch, K, dim)
            else:
                # ablation: one kernel call per node (paper Fig. 6c baseline)
                for item in batch:
                    kernel_calls += self._refresh_batch([item], K, dim)
            refreshes += len(batch)

        # leaves count as refreshed artifacts only for bookkeeping
        for tid, levels in per_tree.items():
            tree = self.trees[tid]
            refreshes += len(levels.get(0, []))
            tree.dirty.clear()

        # root-index rows for dirty trees (derived artifact)
        for tid in targets:
            tree = self.trees[tid]
            self._root_matrix[tree.tree_id] = tree.root_emb()
            self._root_dev_dirty.add(tree.tree_id)
        self.dirty_trees -= targets

        self.summary_refreshes += refreshes
        self.flush_levels += max_level
        return {"refreshes": refreshes, "levels": max_level, "kernel_calls": kernel_calls}

    def _refresh_batch(self, batch: List[Tuple[TreeArena, int]], K: int, dim: int) -> int:
        P = len(batch)
        # pad the parent dim to a power-of-two bucket: the jit-compile set for
        # the refresh kernel stays O(log P_max) across the system's lifetime.
        # With a mesh attached the bucket additionally pads to a shard
        # multiple so the cross-tree batch splits evenly over the data axis.
        cap = 1
        while cap < P:
            cap *= 2
        if self.mesh is not None:
            cap = shard_ops.pad_rows(cap, self._shards())
        with self.obs.span("forest.tree_refresh", parents=P, padded=cap):
            child_emb = np.zeros((cap, K, dim), np.float32)
            mask = np.zeros((cap, K), np.float32)
            for i, (tree, n) in enumerate(batch):
                kids = tree.children[n][:K]
                for j, c in enumerate(kids):
                    child_emb[i, j] = tree.emb[c]
                    mask[i, j] = 1.0
            with self.obs.span("forest.tree_refresh.device"):
                if self.mesh is not None:
                    out = np.asarray(shard_ops.sharded_tree_refresh(
                        child_emb, mask, mesh=self.mesh, axis=self.mesh_axis,
                        impl=self.kernel_impl))
                else:
                    out = np.asarray(ops.tree_refresh(
                        jnp.asarray(child_emb), jnp.asarray(mask),
                        impl=self.kernel_impl))
            for i, (tree, n) in enumerate(batch):
                tree.emb[n] = out[i]
                tree.refresh_text(n)
        return 1

    def eager_refresh_path(self, scope_key: str) -> int:
        """Ablation baseline (paper Fig. 6a): refresh the dirty path of one
        tree immediately, one node per call, bottom-up. Returns #calls."""
        tree = self.trees[scope_key]
        levels = tree.dirty_by_level()
        calls = 0
        for lam in sorted(l for l in levels if l >= 1):
            for n in levels[lam]:
                calls += self._refresh_batch([(tree, n)], self.config.branching_factor,
                                             self.config.embed_dim)
        tree.dirty.clear()
        self._root_matrix[tree.tree_id] = tree.root_emb()
        self._root_dev_dirty.add(tree.tree_id)
        self.dirty_trees.discard(scope_key)
        self.summary_refreshes += calls
        return calls

    # ------------------------------------------------------------------
    # derived-index views (retrieval reads these)
    # ------------------------------------------------------------------
    def root_index(self) -> Tuple[np.ndarray, int, List[str]]:
        """(capacity-padded matrix, valid count, tree order)."""
        return self._root_matrix, len(self._tree_order), list(self._tree_order)

    def fact_index(self) -> Tuple[np.ndarray, int]:
        """(capacity-padded matrix, valid count). Dead facts' rows are zeroed
        on deletion; callers filter by fact_alive."""
        return self.fact_emb, len(self.facts)

    def set_root_row(self, tree: TreeArena) -> None:
        """Write a tree's root-index row (host + device invalidation) — the
        one sanctioned way to edit ``_root_matrix`` outside flush()."""
        self._root_matrix[tree.tree_id] = tree.root_emb()
        self._root_dev_dirty.add(tree.tree_id)

    # ------------------------------------------------------------------
    # multi-device serve (mesh-sharded index + flush batches)
    # ------------------------------------------------------------------
    def set_mesh(self, mesh, axis: str = "data") -> None:
        """Attach a serve mesh: the fact index shards round-robin over the
        mesh's ``axis`` (kernels/shard_ops layout), the root index
        replicates, and flush/browse batches run shard-mapped. ``None`` (or
        a mesh whose data axis is width 1) restores the single-device fast
        path. Resets the device caches so the next sync uploads with the new
        layout; persistent state is untouched, so results are identical
        across any mesh change (tests/test_sharded_serve.py)."""
        if mesh is not None and shard_ops.mesh_shards(mesh, axis) <= 1:
            mesh = None
        self.mesh = mesh
        self.mesh_axis = axis
        self._fact_dev = None
        self._fact_dev_rows = 0
        self._fact_dev_dirty.clear()
        self._root_dev = None
        self._root_dev_rows = 0
        self._root_dev_dirty.clear()

    def _shards(self) -> int:
        return shard_ops.mesh_shards(self.mesh, self.mesh_axis)

    # ------------------------------------------------------------------
    # residency: device-cache detach (tenant demotion) + footprint
    # ------------------------------------------------------------------
    def device_bytes(self) -> int:
        """Bytes currently held by the device-resident index caches (the
        capacity-padded arenas, f32). 0 when detached / never materialized."""
        total = 0
        for arr in (self._fact_dev, self._root_dev):
            if arr is not None:
                total += int(np.prod(arr.shape)) * 4
        return total

    def estimated_device_bytes(self) -> int:
        """Host-side footprint estimate (index rows x dim x 4B) — what the
        caches WOULD occupy once materialized. The residency budget planner
        uses this so a hot-but-not-yet-queried tenant still counts against
        the device budget."""
        return 4 * self.config.embed_dim * (
            int(self.fact_emb.shape[0]) + int(self._root_matrix.shape[0]))

    def detach_device(self) -> int:
        """Tenant demotion: eagerly free both device index caches
        (``ops.release_rows``; ``index_releases`` counts freed arenas,
        mirroring ``index_grows``) and return the bytes released.

        Reattachment is transparent — the next ``fact_index_device()`` /
        ``root_index_device()`` call re-uploads from host state exactly like
        a freshly loaded snapshot, so only the rehydrated tenant's rows ever
        transfer (other tenants' caches are untouched). Persistent and host
        derived state are unaffected; results are identical across a
        detach/reattach round-trip."""
        freed = self.device_bytes()
        for arr in (self._fact_dev, self._root_dev):
            if arr is not None:
                ops.release_rows(arr)
                self.index_releases += 1
        self._fact_dev = None
        self._fact_dev_rows = 0
        self._fact_dev_dirty.clear()
        self._root_dev = None
        self._root_dev_rows = 0
        self._root_dev_dirty.clear()
        return freed

    # ------------------------------------------------------------------
    # device-resident normalized index views (retrieval hot path)
    # ------------------------------------------------------------------
    def _sync_device(self, host: np.ndarray, n: int, cached, synced_rows: int,
                     dirty: Set[int], *, sharded: bool = False):
        """Bring one device index cache up to date with its host matrix.
        Returns (device array, new synced row count).

        Capacity growth is geometric and device-side: when the host matrix
        outgrows the cached buffer, the buffer gains zero rows IN PLACE
        (ops.grow_rows / shard_ops.grow_sharded) and only new/dirty rows are
        scattered — steady ingest never re-uploads or re-normalizes the
        whole index. Full uploads happen only on first use, dtype/dim
        change, shrink (snapshot restore), or mesh change.

        ``sharded=True`` (the fact index) uses the round-robin sharded
        layout when a mesh is attached; the root index stays replicated."""
        mesh = self.mesh if sharded else None
        S = shard_ops.mesh_shards(mesh, self.mesh_axis)
        cap = shard_ops.pad_rows(host.shape[0], S)
        if cached is not None and (cached.shape[1] != host.shape[1]
                                   or cached.shape[0] > cap):
            cached = None
        if cached is None:
            self.index_uploads += 1
            dirty.clear()
            if mesh is not None:
                return shard_ops.upload_sharded(host, cap, mesh,
                                                self.mesh_axis), n
            if self.mesh is not None:
                return shard_ops.upload_replicated(host, self.mesh), n
            return ops.normalize_rows(jnp.asarray(host)), n
        if cached.shape[0] < cap:
            self.index_grows += 1
            if mesh is not None:
                cached = shard_ops.grow_sharded(cached, cap, mesh,
                                                self.mesh_axis)
            else:
                cached = ops.grow_rows(cached, cap - cached.shape[0])
        rows = sorted(set(r for r in dirty if r < n)
                      | set(range(synced_rows, n)))
        dirty.clear()
        if not rows:
            return cached, n
        # bucket the update size: the jit-compile set for the scatter stays
        # O(log U_max); padding entries carry a drop sentinel (out-of-bounds
        # index single-device, -1 in the sharded layout)
        ucap = 1
        while ucap < len(rows):
            ucap *= 2
        sentinel = -1 if mesh is not None else host.shape[0]
        idx = np.full(ucap, sentinel, np.int32)
        idx[: len(rows)] = rows
        upd = np.zeros((ucap, host.shape[1]), np.float32)
        upd[: len(rows)] = host[rows]
        self.index_row_updates += 1
        if mesh is not None:
            return shard_ops.sharded_scatter_rows(
                cached, idx, upd, mesh=mesh, axis=self.mesh_axis), n
        return ops.scatter_normalize_rows(
            cached, jnp.asarray(idx), jnp.asarray(upd)), n

    def fact_index_device(self):
        """(device-resident L2-normalized fact matrix, valid count). Use with
        ``topk_sim(..., normalize=False)``; rows are normalized with the same
        formula the kernel applies, so scores match the host path bit-for-
        bit. Dead facts' rows are zero vectors (score 0 after masking).

        With a mesh attached the matrix is round-robin row-sharded and must
        be scanned through ``shard_ops.sharded_topk_sim`` (which returns
        global row ids); the Retriever dispatches on ``forest.mesh``."""
        n = len(self.facts)
        self._fact_dev, self._fact_dev_rows = self._sync_device(
            self.fact_emb, n, self._fact_dev, self._fact_dev_rows,
            self._fact_dev_dirty, sharded=True)
        return self._fact_dev, n

    def root_index_device(self):
        """(device-resident normalized root matrix, valid count, tree order).
        Same contract as fact_index_device for the tree-root index."""
        n = len(self._tree_order)
        self._root_dev, self._root_dev_rows = self._sync_device(
            self._root_matrix, n, self._root_dev, self._root_dev_rows,
            self._root_dev_dirty)
        return self._root_dev, n, list(self._tree_order)

    # ------------------------------------------------------------------
    # scene routing state
    # ------------------------------------------------------------------
    def route_scene(self, emb: np.ndarray) -> int:
        """Nearest-centroid online clustering; returns scene id."""
        thr = self.config.scene_sim_threshold
        if self.scene_centroids.shape[0]:
            sims = self.scene_centroids @ emb
            best = int(np.argmax(sims))
            if sims[best] >= thr:
                c = self.scene_counts[best]
                self.scene_centroids[best] = (self.scene_centroids[best] * c + emb) / (c + 1)
                norm = np.linalg.norm(self.scene_centroids[best]) + 1e-6
                self.scene_centroids[best] /= norm
                self.scene_counts[best] += 1
                return best
        self.scene_centroids = np.concatenate([self.scene_centroids, emb[None]], axis=0)
        self.scene_counts.append(1)
        return self.scene_centroids.shape[0] - 1

    # ------------------------------------------------------------------
    # stats
    # ------------------------------------------------------------------
    def scale_stats(self) -> Dict[str, int]:
        return {
            "facts": sum(self.fact_alive),
            "trees": sum(1 for t in self.trees.values() if t.root >= 0),
            "nodes": sum(t.num_nodes for t in self.trees.values()),
            "cells": len(self.cells),
        }
