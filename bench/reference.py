"""Plain references of the memory kernels: ``topk_sim``, ``browse_scores``
and ``tree_refresh``, their semantics in float64 on the host, and their
bfloat16 controls. They import nothing of the program.

The model's reference (``forward``, ``embed``) depends on its architecture
and lives in the configuration's ``bench/arch/<arch>.py``.
"""
from __future__ import annotations

import numpy as np


def _bf16(x):
    import jax.numpy as jnp

    return np.asarray(jnp.asarray(x, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def _unit(x):
    return x / (np.linalg.norm(x, axis=-1, keepdims=True) + 1e-6)


def topk_scores(queries, keys, normalize: bool, num_valid, *, bf16=False):
    q = np.asarray(queries, np.float64)
    k = np.asarray(keys, np.float64)
    if normalize:
        q, k = _unit(q), _unit(k)
    if bf16:
        q, k = _bf16(q), _bf16(k)
    s = q @ k.T
    if num_valid is not None:
        s[:, int(num_valid):] = -np.inf
    return s


def browse_scores(child, q, mask, *, bf16=False):
    c, qq = np.asarray(child, np.float64), np.asarray(q, np.float64)
    if bf16:
        c, qq = _bf16(c), _bf16(qq)
    return np.einsum("fkd,fd->fk", c, qq) * np.asarray(mask, np.float64)


def tree_refresh(child, mask, *, bf16=False):
    c = np.asarray(child, np.float64)
    if bf16:
        c = _bf16(c)
    m = np.asarray(mask, np.float64)[..., None]
    mean = (c * m).sum(1) / np.maximum(m.sum(1), 1.0)
    return _unit(mean)
