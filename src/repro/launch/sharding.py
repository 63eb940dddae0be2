"""Logical sharding rules (MaxText-style) for the production mesh.

Mesh axes: ("pod", "data", "model") multi-pod, ("data", "model") single-pod.
`pod`+`data` are the data-parallel axes; `model` is tensor/expert-parallel.

Models call :func:`constrain` on activations with *logical* specs; axes not
present in the ambient mesh are silently dropped, so the same model code runs
on any mesh (including none — smoke tests on one CPU device).

Parameter shardings are name-based: :func:`param_pspec` maps a param path to
a PartitionSpec, and :func:`param_shardings` builds the full pytree used as
``in_shardings`` at jit time.
"""
from __future__ import annotations

import re
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

DATA_AXES: Tuple[str, ...] = ("pod", "data")
MODEL_AXIS = "model"


def current_mesh_axes() -> Tuple[str, ...]:
    am = jax.sharding.get_abstract_mesh()
    return () if am.empty else tuple(am.axis_names)


def _clean_spec(spec, names) -> P:
    out = []
    for s in spec:
        if s is None:
            out.append(None)
        elif isinstance(s, str):
            out.append(s if s in names else None)
        else:
            t = tuple(a for a in s if a in names)
            out.append(t if t else None)
    return P(*out)


def constrain(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that tolerates absent mesh axes / no mesh."""
    names = current_mesh_axes()
    if not names:
        return x
    return jax.lax.with_sharding_constraint(x, _clean_spec(spec, names))


def batch_spec(*rest) -> Tuple:
    """Leading batch dim sharded over all data axes."""
    return (DATA_AXES,) + rest


# ---------------------------------------------------------------------------
# parameter sharding rules (name-based; first match wins)
# ---------------------------------------------------------------------------
# Conventions (see models/*):
#   wq/wk/wv: (D, H*Dh)  -> shard output (head) dim over model
#   wo:       (H*Dh, D)  -> shard input (head) dim over model
#   w_gate/w_up/wi: (D, F) -> shard F over model
#   w_down/wd:      (F, D) -> shard F over model
#   MoE expert weights: (E, D, F)/(E, F, D) -> shard E over model
#   router: (D, E) -> replicated (small)
#   embed: (V, D) -> shard V over model; unembed (D, V) -> shard V
#   norms / biases / scalars -> replicated
#   rwkv/mamba projections: (D, X) -> X over model; conv/ssm per-channel
#   params with leading scan-layer dim L get None prepended via _trail


def _trail(nd: int, *spec) -> P:
    """PartitionSpec with `spec` on the trailing len(spec) dims."""
    pad = (None,) * (nd - len(spec))
    return P(*(pad + spec))


def param_pspec(path: str, leaf: Any, *, moe_fsdp: bool = True) -> P:
    nd = leaf.ndim if hasattr(leaf, "ndim") else 0
    name = path.split("/")[-1]
    if nd <= 1:
        return P()
    # embeddings: shard vocab dim over model
    if name == "embed":
        return _trail(nd, MODEL_AXIS, None)
    if name == "unembed":
        return _trail(nd, None, MODEL_AXIS)
    # attention projections
    if name in ("wq", "wk", "wv", "w_kv_cross_k", "w_kv_cross_v"):
        return _trail(nd, None, MODEL_AXIS)
    if name == "wo":
        return _trail(nd, MODEL_AXIS, None)
    # MoE experts: (E, D, F) / (E, F, D) — expert dim over model, second dim
    # FSDP-sharded over the data axes (a 235B-A22B's expert weights are the
    # bulk of its 470GB; without FSDP they exceed per-chip HBM). Serving
    # uses pure EP (moe_fsdp=False) to avoid per-step weight gathers.
    if name in ("we_gate", "we_up", "we_down"):
        return _trail(nd, MODEL_AXIS, DATA_AXES if moe_fsdp else None, None)
    if name == "router":
        return P()
    # MLP
    if name in ("w_gate", "w_up", "wi"):
        return _trail(nd, None, MODEL_AXIS)
    if name in ("w_down", "wd"):
        return _trail(nd, MODEL_AXIS, None)
    # rwkv time-mix / channel-mix projections (D, D) or (D, F)
    if name in ("wr", "wk_t", "wv_t", "wg", "w_cm_k"):
        return _trail(nd, None, MODEL_AXIS)
    if name in ("wo_t", "w_cm_v"):
        return _trail(nd, MODEL_AXIS, None)
    # mamba
    if name == "w_in":
        return _trail(nd, None, MODEL_AXIS)
    if name == "w_out":
        return _trail(nd, MODEL_AXIS, None)
    # default: replicate
    return P()


def _path_str(keypath) -> str:
    parts = []
    for k in keypath:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _drop_indivisible(spec: P, leaf, mesh: Mesh) -> P:
    """Remove mesh axes from dims they don't divide evenly (e.g. a 51865
    vocab can't shard 16 ways — replicate that dim instead of failing)."""
    if not hasattr(leaf, "shape"):
        return spec
    sizes = dict(mesh.shape)
    out = []
    for i, s in enumerate(tuple(spec)):
        if s is None:
            out.append(None)
            continue
        axes = (s,) if isinstance(s, str) else tuple(s)
        width = 1
        for a in axes:
            width *= sizes.get(a, 1)
        out.append(s if leaf.shape[i] % width == 0 else None)
    return P(*out)


def param_shardings(mesh: Mesh, params_tree: Any, *, moe_fsdp: bool = True,
                    kv_heads: int = 0) -> Any:
    """Pytree of NamedShardings matching `params_tree` (arrays or SDS).

    kv_heads: when > 0 and not divisible by the TP width, the wk/wv
    projections are REPLICATED (a few MB/layer) instead of column-sharded —
    otherwise every layer's k/v activations get all-gathered across the
    model axis (GQA kv narrower than TP; see EXPERIMENTS.md §Perf)."""
    tp = dict(mesh.shape).get(MODEL_AXIS, 1)
    kv_replicate = kv_heads > 0 and kv_heads % tp != 0

    def one(keypath, leaf):
        path = _path_str(keypath)
        name = path.split("/")[-1]
        if kv_replicate and name in ("wk", "wv"):
            return NamedSharding(mesh, P())
        spec = param_pspec(path, leaf, moe_fsdp=moe_fsdp)
        # drop axes absent from this mesh, then indivisible placements
        spec = _clean_spec(tuple(spec), tuple(mesh.axis_names))
        spec = _drop_indivisible(spec, leaf, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_tree)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def data_sharding(mesh: Mesh, ndim: int, batch_size: Optional[int] = None) -> NamedSharding:
    axes = tuple(a for a in DATA_AXES if a in mesh.axis_names)
    if batch_size is not None and axes:
        width = 1
        for a in axes:
            width *= dict(mesh.shape)[a]
        if batch_size % width != 0:
            # batch too small/ragged for full DP: replicate (e.g. the
            # long_500k single-sequence decode cell)
            axes = ()
    return NamedSharding(mesh, P(axes if axes else None, *([None] * (ndim - 1))))


def zero1_pspec(path: str, leaf: Any, dp_size: int = 0) -> P:
    """Optimizer-moment sharding (ZeRO-1): the param spec plus the data axes
    on the LARGEST free dim that divides evenly by the DP width. Falls back
    to the plain param spec if no dim qualifies (e.g. layer-stacked scalars).
    """
    base = tuple(param_pspec(path, leaf))
    nd = leaf.ndim if hasattr(leaf, "ndim") else 0
    base = base + (None,) * (nd - len(base))
    out = list(base)
    # FSDP-sharded params already consume the data axes
    if any(s == DATA_AXES for s in out):
        return P(*out)
    if hasattr(leaf, "shape") and dp_size > 0:
        best, best_size = -1, 0
        for i, s in enumerate(out):
            if s is None and leaf.shape[i] % dp_size == 0 and leaf.shape[i] > best_size:
                best, best_size = i, leaf.shape[i]
        if best >= 0:
            out[best] = DATA_AXES
    return P(*out)


def zero1_shardings(mesh: Mesh, params_tree: Any) -> Any:
    sizes = dict(mesh.shape)
    dp = 1
    for a in DATA_AXES:
        dp *= sizes.get(a, 1)

    def one(keypath, leaf):
        spec = zero1_pspec(_path_str(keypath), leaf, dp_size=dp)
        spec = _clean_spec(tuple(spec), tuple(mesh.axis_names))
        spec = _drop_indivisible(spec, leaf, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, params_tree)
