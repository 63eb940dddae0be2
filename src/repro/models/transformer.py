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
# init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_layers, k_out = jax.random.split(key, 3)

    def init_layer(k):
        ka, km, = jax.random.split(k, 2)
        p = {
            "ln1": jnp.ones((cfg.d_model,), dtype),
            "ln2": jnp.ones((cfg.d_model,), dtype),
            "attn": L.attn_init(ka, cfg, dtype),
        }
        if cfg.family == "moe":
            p["moe"] = L.moe_init(km, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(km, cfg, dtype)
        return p

    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    stacked = jax.vmap(init_layer)(layer_keys)

    params: Dict[str, Any] = {
        "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dtype),
        "layers": stacked,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = L.dense_init(k_out, cfg.d_model, cfg.vocab_size, dtype)
    return params


# ---------------------------------------------------------------------------
# shared trunk
# ---------------------------------------------------------------------------
def _layer_fwd(cfg: ModelConfig, p, x, positions):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    x = x + L.attention_prefill(p["attn"], h, cfg, positions)
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = L.moe_block(p["moe"], h, cfg)
    else:
        y, aux = L.mlp_block(p["mlp"], h, cfg), jnp.asarray(0.0, jnp.float32)
    return x + y, aux


def trunk(params, cfg: ModelConfig, x: jax.Array, positions: jax.Array):
    """x: (B, S, D) embeddings -> (hidden (B, S, D), aux_loss)."""

    def body(carry, p):
        x = carry
        fwd = functools.partial(_layer_fwd, cfg)
        if cfg.remat:
            fwd = jax.checkpoint(fwd)
        x, aux = fwd(p, x, positions)
        return x, aux

    if cfg.scan_layers:
        x, auxs = jax.lax.scan(body, x, params["layers"])
        aux = jnp.sum(auxs)
    else:
        aux = jnp.asarray(0.0, jnp.float32)
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a, _i=i: a[_i], params["layers"])
            x, a = body(x, p)
            aux = aux + a
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), aux


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
    h, aux = trunk(params, cfg, x, positions)
    if cfg.family == "vlm" and "patch_embeds" in batch:
        P = batch["patch_embeds"].shape[1]
        h = h[:, P:]
    logits = _logits(params, cfg, h)
    loss = L.softmax_xent(logits, batch["labels"], batch.get("loss_mask"))
    return loss + 0.01 * aux, {"xent": loss, "aux": aux}


# ---------------------------------------------------------------------------
# serving: prefill + decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, jax.Array]:
    dtype = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    dtype = jnp.dtype(cfg.dtype)
    shape = (cfg.num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
        "lengths": jax.ShapeDtypeStruct((batch,), jnp.int32),
    }


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

    def body(carry, p):
        x = carry

        def fwd(p, x):
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            att, (k, v) = L.attention_prefill(
                p["attn"], h, cfg, positions, return_kv=True
            )
            x = x + att
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = L.moe_block(p["moe"], h, cfg)
            else:
                y = L.mlp_block(p["mlp"], h, cfg)
            return x + y, (k, v)

        if cfg.remat:
            fwd = jax.checkpoint(fwd)
        x, kv = fwd(p, x)
        return x, kv

    x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
    lengths = batch.get("lengths")
    if lengths is None:
        lengths = jnp.full((B,), S, jnp.int32)
    last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)[:, 0]
    h = L.rms_norm(last, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, h)

    pad = max_len - S
    if pad > 0:
        ks = jnp.pad(ks, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
        vs = jnp.pad(vs, ((0, 0), (0, 0), (0, pad), (0, 0), (0, 0)))
    cache = {
        "k": ks.astype(jnp.dtype(cfg.dtype)),
        "v": vs.astype(jnp.dtype(cfg.dtype)),
        "lengths": lengths.astype(jnp.int32),
    }
    return logits, cache


def decode_step(params, cfg: ModelConfig, batch: Dict[str, jax.Array], cache):
    """One-token decode. batch: {"tokens": (B,) int32} (+ patch stub ignored).
    Returns (logits (B, V), new cache)."""
    tok = batch["tokens"]
    x = params["embed"][tok]                       # (B, D)
    x = constrain(x, DATA_AXES, None)
    lengths = cache["lengths"]

    def body(carry, scanned):
        x = carry
        p, kc, vc = scanned

        def fwd(p, x, kc, vc):
            h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
            att, kc2, vc2 = L.attention_decode(p["attn"], h, cfg, kc, vc, lengths)
            x = x + att
            h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
            if cfg.family == "moe":
                y, _ = L.moe_block(p["moe"], h[:, None, :], cfg)
                y = y[:, 0]
            else:
                y = L.mlp_block(p["mlp"], h, cfg)
            return x + y, kc2, vc2

        x, kc2, vc2 = fwd(p, x, kc, vc)
        return x, (kc2, vc2)

    x, (ks, vs) = jax.lax.scan(body, x, (params["layers"], cache["k"], cache["v"]))
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(params, cfg, h)
    new_cache = {"k": ks, "v": vs, "lengths": lengths + 1}
    return logits, new_cache
