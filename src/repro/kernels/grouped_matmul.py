"""Grouped matmul for the dropless expert layer — JAX's Pallas TPU kernel
(``megablox.gmm``) with this layer's tiling.

Rows of ``lhs`` (m, K) lie grouped by expert, group g's rows after group
g-1's, ``group_sizes[g]`` of them; each group is multiplied by its own
``rhs[g]`` (K, N). The grid walks only the row tiles that hold a group's
rows (a dynamic count), so the work follows the rows routed, not m; rows
past ``sum(group_sizes)`` are not written.

Tiling: ``tm`` rows, the whole K in one step (so the weight block of an
expert stays resident while its row tiles pass: every weight is read once
a call), N in tiles of ``tn``. With tm = 256 a tile does 256 FLOP per
weight byte, past the v5e ridge (about 240).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.ops import gmm as _gmm

ROW_TILE = 256
COL_TILE = 512


def tiling(m: int, k: int, n: int):
    return (min(ROW_TILE, m), k, min(COL_TILE, n))


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
                   interpret: bool = False) -> jax.Array:
    m, k = lhs.shape
    # positional: the differentiable wrapper's static arguments
    return _gmm(lhs, rhs, group_sizes.astype(jnp.int32), lhs.dtype,
                tiling(m, k, rhs.shape[2]), None, None, False, interpret)
