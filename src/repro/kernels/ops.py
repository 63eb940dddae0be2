"""Jit'd dispatchers over kernel implementations.

``impl`` selects:
  * ``reference``        — pure-jnp oracle (ref.py). XLA-fused; the path
                           off the TPU.
  * ``pallas``           — the Pallas TPU kernel (TARGET hardware).
  * ``pallas_interpret`` — the same kernel body executed in interpret mode
                           (CPU correctness validation; used by tests).

:func:`resolve_impl` makes the platform decision in one place: callers that
leave ``kernel_impl`` / ``attention_impl`` unset get the Pallas kernels on
TPU and the reference everywhere else. Interpret mode runs only where a
caller names it.

Every dispatcher here is single-device; the mesh-sharded twins (shard-local
launch of the SAME kernels + cheap cross-device merges) live in
``repro.kernels.shard_ops`` and are selected by the Forest/Retriever when a
serve mesh is attached (``Forest.set_mesh``). mesh=None callers never touch
that module — the single-device path below stays byte-identical.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.browse_scores import browse_scores as _browse
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.decode_attention import decode_attention as _decode
from repro.kernels.grouped_matmul import grouped_matmul as _gmm
from repro.kernels.topk_sim import topk_sim as _topk
from repro.kernels.tree_refresh import tree_refresh as _tree_refresh
from repro.kernels.rwkv6_scan import rwkv6_scan as _rwkv6
from repro.kernels.mamba2_ssd import mamba2_ssd as _ssd

VALID_IMPLS = ("reference", "pallas", "pallas_interpret")


def _check(impl: str) -> None:
    if impl not in VALID_IMPLS:
        raise ValueError(f"impl must be one of {VALID_IMPLS}, got {impl!r}")


# Kernels that do not lower for TPU yet: rwkv6_scan's (1, K) bonus block and
# mamba2_ssd's (1, 1) decay block break Mosaic's tiling rule. They run only
# where a caller names a Pallas impl.
NOT_ON_TPU = frozenset({"rwkv6_scan", "mamba2_ssd"})


def resolve_impl(impl: Optional[str] = None,
                 kernel: Optional[str] = None) -> str:
    """``impl`` if named, else the platform's: ``"pallas"`` when JAX's
    default backend is a TPU and ``kernel`` (a dispatcher's name) lowers
    there, ``"reference"`` otherwise."""
    if impl is None:
        on_tpu = jax.default_backend() == "tpu" and kernel not in NOT_ON_TPU
        impl = "pallas" if on_tpu else "reference"
    _check(impl)
    return impl


@functools.partial(jax.jit, static_argnames=("causal", "impl", "block_q", "block_kv",
                                             "sm_scale"))
def attention(q, k, v, *, causal=True, impl="reference", block_q=512, block_kv=512,
              sm_scale=None):
    """q, k (B, S, H, Dk), v (B, S, H, Dv); ``sm_scale`` None = Dk^-1/2."""
    _check(impl)
    if impl == "reference":
        return _ref.attention_ref(q, k, v, causal=causal, sm_scale=sm_scale)
    return _flash(
        q, k, v,
        causal=causal,
        block_q=block_q,
        block_kv=block_kv,
        sm_scale=sm_scale,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def grouped_matmul(lhs, rhs, group_sizes, *, impl="reference"):
    """(m, K) rows grouped by ``group_sizes`` times their group's (K, N)
    matrix of ``rhs`` (G, K, N) -> (m, N) in lhs's dtype; rows past
    ``sum(group_sizes)`` are undefined."""
    _check(impl)
    if impl == "reference":
        return _ref.grouped_matmul_ref(lhs, rhs, group_sizes)
    return _gmm(lhs, rhs, group_sizes, interpret=(impl == "pallas_interpret"))


@functools.partial(jax.jit, static_argnames=("impl", "block_kv"))
def decode_attention(q, k_cache, v_cache, lengths, *, impl="reference", block_kv=1024):
    _check(impl)
    if impl == "reference":
        return _ref.decode_attention_ref(q, k_cache, v_cache, lengths)
    return _decode(
        q, k_cache, v_cache, lengths,
        block_kv=block_kv,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(jax.jit, static_argnames=("k", "normalize", "impl"))
def topk_sim(queries, keys, k, *, normalize=True, num_valid=None, impl="reference"):
    _check(impl)
    if impl == "reference":
        return _ref.topk_sim_ref(queries, keys, k, normalize=normalize,
                                 num_valid=num_valid)
    return _topk(
        queries, keys, k,
        normalize=normalize,
        num_valid=num_valid,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(jax.jit, static_argnames=("impl",))
def browse_scores(child_emb, q_emb, child_mask, *, impl="reference"):
    """One browse depth level: per-frontier-entry masked child scoring.
    child_emb (F, K, D), q_emb (F, D), child_mask (F, K) -> (F, K) f32."""
    _check(impl)
    if impl == "reference":
        return _ref.browse_scores_ref(child_emb, q_emb, child_mask)
    return _browse(
        child_emb, q_emb, child_mask, interpret=(impl == "pallas_interpret")
    )


# ---------------------------------------------------------------------------
# device-resident index maintenance (used by Forest's normalized index cache)
# ---------------------------------------------------------------------------
@jax.jit
def normalize_rows(x):
    """L2-normalize rows with the same formula topk_sim uses in-kernel, so a
    pre-normalized device index + ``normalize=False`` is numerically
    equivalent to passing the raw matrix with ``normalize=True``."""
    xf = x.astype(jnp.float32)
    return xf / (jnp.linalg.norm(xf, axis=-1, keepdims=True) + 1e-6)


@jax.jit
def scatter_normalize_rows(arr, idx, rows):
    """Incremental device-index update: write normalized ``rows`` at ``idx``
    in the cached matrix. Padding entries carry idx == arr.shape[0] (out of
    bounds) and are dropped, so callers can bucket the update size. ``arr``
    is deliberately NOT donated: previously returned index views must stay
    valid after a later sync (donation would delete their buffer on
    accelerator backends)."""
    rf = rows.astype(jnp.float32)
    rf = rf / (jnp.linalg.norm(rf, axis=-1, keepdims=True) + 1e-6)
    return arr.at[idx].set(rf, mode="drop")


@functools.partial(jax.jit, static_argnames=("add",))
def grow_rows(arr, add):
    """Geometric device-cache growth (single-device path): append ``add``
    zero rows to a cached index matrix ON DEVICE. Capacity growth used to
    invalidate the whole cache and re-upload + re-normalize every row from
    host; this keeps the existing normalized rows in place so only new/dirty
    rows transfer (Forest._sync_device). Not donated, for the same
    view-validity reason as scatter_normalize_rows."""
    return jnp.concatenate(
        [arr, jnp.zeros((add, arr.shape[1]), arr.dtype)])


@functools.partial(jax.jit, static_argnames=("keep",))
def _shrink_rows(arr, keep):
    """Copy rows [0, keep) into a fresh (smaller) buffer so the oversized
    arena can be deleted. Callers bucket ``keep`` (power of two) to bound the
    jit-compile set, mirroring grow_rows' geometric policy."""
    return jnp.array(arr[:keep])


def _delete_buffer(arr) -> None:
    """Eagerly free a device buffer. Dropping the Python reference leaves
    the buffer alive until GC runs; at residency-eviction rates that is
    exactly the device-memory leak the hot budget exists to prevent."""
    delete = getattr(arr, "delete", None)
    if delete is None:
        return
    try:
        delete()
    except Exception:
        pass    # already deleted / backend without explicit free


def release_rows(arr, keep: int = 0):
    """Inverse of grow_rows: release device rows held by a cached index.

    ``keep=0`` (tenant demotion) frees the whole buffer eagerly and returns
    None — the caller drops its reference and the next index access is a
    fresh upload. ``keep=n`` shrinks the geometric-growth arena: rows
    [0, n) move into a fresh buffer (materialized before the old one is
    deleted), the oversized arena is freed, and the shrunk buffer is
    returned. Not jitted end-to-end: the delete is a host-side buffer
    operation, so only the copy is compiled (``_shrink_rows``)."""
    if arr is None:
        return None
    if keep <= 0:
        _delete_buffer(arr)
        return None
    out = _shrink_rows(arr, keep)
    jax.block_until_ready(out)
    _delete_buffer(arr)
    return out


@functools.partial(jax.jit, static_argnames=("impl",))
def tree_refresh(child_emb, child_mask, *, impl="reference"):
    _check(impl)
    if impl == "reference":
        return _ref.tree_refresh_ref(child_emb, child_mask)
    return _tree_refresh(
        child_emb, child_mask, interpret=(impl == "pallas_interpret")
    )


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def rwkv6_scan(r, k, v, w, u, state, *, impl="reference", chunk=64):
    _check(impl)
    if impl == "reference":
        return _ref.rwkv6_scan_ref(r, k, v, w, u, state)
    return _rwkv6(
        r, k, v, w, u, state,
        chunk=chunk,
        interpret=(impl == "pallas_interpret"),
    )


@functools.partial(jax.jit, static_argnames=("impl", "chunk"))
def mamba2_ssd(x, dt, A, Bm, C, state, *, impl="reference", chunk=64):
    _check(impl)
    if impl == "reference":
        return _ref.mamba2_ssd_ref(x, dt, A, Bm, C, state)
    return _ssd(
        x, dt, A, Bm, C, state,
        chunk=chunk,
        interpret=(impl == "pallas_interpret"),
    )
