"""Decoder-only LM: dense, MoE, and VLM (patch-embedding stub) families.

Layers are stacked along a leading dim and scanned (`lax.scan`) with
optional remat — keeps the HLO size O(1) in depth, which matters both for
94-layer MoE dry-run compiles and for real-TPU compile latency.

Three entry points per model (see factory.Model):
  * loss(params, batch)                  — train forward + xent
  * prefill(params, batch)               — returns (last-token logits, cache)
  * decode(params, batch, cache)         — one token against the cache
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.launch.sharding import DATA_AXES, MODEL_AXIS, constrain
from repro.models import layers as L


# ---------------------------------------------------------------------------
# layer stacks
# ---------------------------------------------------------------------------
def _stacks(cfg: ModelConfig):
    """The model's layer stacks in order: (params key, cache key prefix,
    whether its MLP is the expert layer, depth). A MoE model's leading
    dense layers (``first_dense_layers``) are a stack of their own."""
    out = []
    if cfg.first_dense_layers:
        out.append(("dense_layers", "dense_", False, cfg.first_dense_layers))
    out.append(("layers", "", cfg.family == "moe",
                cfg.num_layers - cfg.first_dense_layers))
    return out


def _cache_leaves(cfg: ModelConfig, batch: int, max_len: int):
    """Per-layer cache leaves: the latent (``c_kv``, ``k_pe``) for latent
    attention, else per-head ``k`` and ``v``; shapes (batch, max_len, ...)."""
    if cfg.is_mla:
        return {"c_kv": (batch, max_len, cfg.kv_lora_rank),
                "k_pe": (batch, max_len, cfg.qk_rope_head_dim)}
    shape = (batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {"k": shape, "v": shape}


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_out = jax.random.split(key, 3)

    def init_layer(k, moe: bool):
        ka, km, = jax.random.split(k, 2)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": L.attn_init(ka, cfg, dtype),
        }
        if moe:
            p["moe"] = L.moe_init(km, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(km, cfg, dtype, d_ff=cfg.dense_d_ff or None)
        return p

    params: Dict[str, Any] = {
        "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    for i, (name, _prefix, moe, depth) in enumerate(_stacks(cfg)):
        # the first stack keeps the keys a single-stack model always had
        keys = jax.random.split(jax.random.fold_in(k_layers, i) if i else k_layers, depth)
        params[name] = jax.vmap(functools.partial(init_layer, moe=moe))(keys)
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# shared trunk
# ---------------------------------------------------------------------------
def _layer_fwd(cfg: ModelConfig, moe: bool, collect_kv: bool, p, x, positions):
    """One layer. Returns (x, (aux loss, expert rows, cache rows or None))."""
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    attend = L.mla_prefill if cfg.is_mla else L.attention_prefill
    att = attend(p["attn"], h, cfg, positions, return_kv=collect_kv)
    kv = None
    if collect_kv:
        att, kv = att
    x = x + att
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if moe:
        y, aux, rows = L.moe_block(p["moe"], h, cfg)
    else:
        y = L.mlp_block(p["mlp"], h, cfg)
        aux, rows = jnp.asarray(0.0, jnp.float32), jnp.asarray(0, jnp.int32)
    return x + y, (aux, rows.astype(jnp.int32), kv)


def _run(params, cfg: ModelConfig, x, positions, collect_kv: bool):
    """Every stack over x. Returns (x, aux, expert rows, cache rows per
    stack: {prefix: (leaf, leaf)} when ``collect_kv``)."""
    aux = jnp.asarray(0.0, jnp.float32)
    rows = jnp.asarray(0, jnp.int32)
    kvs = {}
    for name, prefix, moe, depth in _stacks(cfg):
        fwd = functools.partial(_layer_fwd, cfg, moe, collect_kv)
        if cfg.remat:
            fwd = jax.checkpoint(fwd)

        def body(carry, p, fwd=fwd):
            return fwd(p, carry, positions)

        if cfg.scan_layers or collect_kv:
            x, (a, r, kv) = jax.lax.scan(body, x, params[name])
            aux, rows = aux + jnp.sum(a), rows + jnp.sum(r)
            kvs[prefix] = kv
        else:
            for i in range(depth):
                p = jax.tree.map(lambda a, _i=i: a[_i], params[name])
                x, (a, r, _) = body(x, p)
                aux, rows = aux + a, rows + r
    return x, aux, rows, kvs


def trunk(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) embeddings -> (hidden (B, S, D), aux_loss, rows the
    expert layers computed here, summed over layers; 0 without experts)."""
    x, aux, rows, _ = _run(params, cfg, x, positions, False)
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux, rows


def _logits(params, cfg: ModelConfig, h: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = h @ params["embed"].T
    else:
        logits = h @ params["unembed"]
    if cfg.logits_softcap > 0:
        c = cfg.logits_softcap
        logits = jnp.tanh(logits / c) * c
    return constrain(logits, DATA_AXES, None, MODEL_AXIS) if logits.ndim == 3 else logits


def _embed_batch(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    """Token embedding (+ VLM patch prepend). Returns (x, label_mask_extra)."""
    tokens = batch["tokens"]
    x = params["embed"][tokens]
    if cfg.family == "vlm" and "patch_embeds" in batch:
        patches = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
    return constrain(x, DATA_AXES, None, None)


# ---------------------------------------------------------------------------
# train loss
# ---------------------------------------------------------------------------
def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    x = _embed_batch(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    h, aux, _ = trunk(params, cfg, x, positions)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        P = batch["patch_embeds"].shape[1]
        h = h[:, P:]
    logits = _logits(params, cfg, h)
    loss = L.softmax_xent(logits, batch["labels"], batch.get("loss_mask"))
    return loss + 0.01 * aux, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------
def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """Each stack's per-layer leaves stacked (depth, batch, max_len, ...),
    keyed by the stack's prefix, and ``lengths`` (batch,)."""
    dtype = jnp.dtype(cfg.dtype)
    out = {prefix + leaf: jax.ShapeDtypeStruct((depth,) + shape, dtype)
           for _name, prefix, _moe, depth in _stacks(cfg)
           for leaf, shape in _cache_leaves(cfg, batch, max_len).items()}
    out["lengths"] = jax.ShapeDtypeStruct((batch,), jnp.int32)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, jax.Array]:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_len))


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array], max_len: int):
    """Full-sequence forward; returns (last logits (B, V), cache).

    With ``batch["lengths"]`` (B,) the prompts are left-aligned and padded
    after their ends: the logits are each row's at its last real token, and
    the cache records the true lengths, so decode writes over the padding
    and never attends to it (causal prefill never lets a prompt see it
    either). Without it every row is taken to be S tokens long."""
    x = _embed_batch(params, cfg, batch)
    B, S, _ = x.shape
    positions = jnp.arange(S)[None, :]
    x, _, _, kvs = _run(params, cfg, x, positions, True)
    lengths = batch.get("lengths")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    h = L.rms_norm(last, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, h)

    cache = {}
    leaves = list(_cache_leaves(cfg, B, max_len))
    for prefix, kv in kvs.items():
        for leaf, a in zip(leaves, kv):             # a: (depth, B, S, ...)
            pad = [(0, 0), (0, 0), (0, max_len - S)] + [(0, 0)] * (a.ndim - 3)
            cache[prefix + leaf] = jnp.pad(a, pad).astype(jnp.dtype(cfg.dtype))
    cache["lengths"] = lengths.astype(jnp.int32)
    return logits, cache


def decode_step(params, cfg: ModelConfig, batch: Dict[str, jax.Array], cache):
    """One-token decode. batch: {"tokens": (B,) int32} (+ patch stub ignored).
    Returns (logits (B, V), new cache)."""
    tok = batch["tokens"]
    x = params["embed"][tok]                       # (B, D)
    x = constrain(x, DATA_AXES, None)
    lengths = cache["lengths"]
    leaves = list(_cache_leaves(cfg, 1, 1))
    attend = L.mla_decode if cfg.is_mla else L.attention_decode
    new_cache = {"lengths": lengths + 1}

    for name, prefix, moe, _depth in _stacks(cfg):
        def body(x, scanned, moe=moe):
            p, kc, vc = scanned
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            att, kc2, vc2 = attend(p["attn"], h, cfg, kc, vc, lengths)
            x = x + att
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            if moe:
                y, _, _ = L.moe_block(p["moe"], h[:, None, :], cfg)
                y = y[:, 0]
            else:
                y = L.mlp_block(p["mlp"], h, cfg)
            return x + y, (kc2, vc2)

        x, (ks, vs) = jax.lax.scan(
            body, x, (params[name], cache[prefix + leaves[0]], cache[prefix + leaves[1]]))
        new_cache[prefix + leaves[0]] = ks
        new_cache[prefix + leaves[1]] = vs
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, h)
    return logits, new_cache
