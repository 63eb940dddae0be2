"""RWKV6 "Finch" — attention-free LM with data-dependent decay.

Faithful structure: token-shift lerps for r/k/v/g, a LoRA tower producing the
per-token data-dependent decay w, per-head bonus u, WKV recurrence (chunked —
same math as kernels/rwkv6_scan.py), per-head group-norm on the WKV output,
and squared-ReLU channel-mix. Decode carries (wkv_state, tmix_shift,
cmix_shift) per layer — constant memory in context length, which is why this
arch runs the long_500k cell.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops, ref
from repro.launch.sharding import DATA_AXES, MODEL_AXIS, constrain
from repro.models import layers as L

LORA_DIM = 64


def init_params(cfg: ModelConfig, key: jax.Array) -> Dict[str, Any]:
    dtype = jnp.dtype(cfg.param_dtype)
    D = cfg.d_model
    H = D // cfg.rwkv_head_size
    k_emb, k_layers, k_out = jax.random.split(key, 3)

    def init_layer(k):
        ks = jax.random.split(k, 10)
        return {
            "ln1": jnp.ones((D,), dtype),
            "ln2": jnp.ones((D,), dtype),
            # time-mix
            "mu_r": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "mu_k": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "mu_v": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "mu_w": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "mu_g": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "wr": L.dense_init(ks[0], D, D, dtype),
            "wk_t": L.dense_init(ks[1], D, D, dtype),
            "wv_t": L.dense_init(ks[2], D, D, dtype),
            "wg": L.dense_init(ks[3], D, D, dtype),
            "wo_t": L.dense_init(ks[4], D, D, dtype),
            # data-dependent decay LoRA: w = base + tanh(x @ A) @ B
            "w_base": (jnp.full((D,), -0.5, jnp.float32)).astype(dtype),
            "w_lora_a": L.dense_init(ks[5], D, LORA_DIM, dtype),
            "w_lora_b": (jax.random.normal(ks[6], (LORA_DIM, D), jnp.float32) * 0.01).astype(dtype),
            "u": (jax.random.normal(ks[7], (H, cfg.rwkv_head_size), jnp.float32) * 0.1).astype(dtype),
            "ln_x": jnp.ones((D,), dtype),
            # channel-mix
            "mu_cm": (jnp.zeros((D,), jnp.float32) + 0.5).astype(dtype),
            "w_cm_k": L.dense_init(ks[8], D, cfg.d_ff, dtype),
            "w_cm_v": L.dense_init(ks[9], cfg.d_ff, D, dtype),
        }

    layer_keys = jax.random.split(k_layers, cfg.num_layers)
    return {
        "embed": L.embed_init(k_emb, cfg.vocab_size, D, dtype),
        "ln_in": jnp.ones((D,), dtype),
        "layers": jax.vmap(init_layer)(layer_keys),
        "final_norm": jnp.ones((D,), dtype),
        "unembed": L.dense_init(k_out, D, cfg.vocab_size, dtype),
    }


def _tmix_rkvwg(p, x, shifted, cfg: ModelConfig):
    """Compute r, k, v, w, g from token-shift lerps. x/(B,..,D)."""
    D = cfg.d_model
    H = D // cfg.rwkv_head_size
    hs = cfg.rwkv_head_size

    def lerp(mu):
        return x + (shifted - x) * mu

    r = lerp(p["mu_r"]) @ p["wr"]
    k = lerp(p["mu_k"]) @ p["wk_t"]
    v = lerp(p["mu_v"]) @ p["wv_t"]
    g = jax.nn.silu(lerp(p["mu_g"]) @ p["wg"])
    xw = lerp(p["mu_w"])
    w = p["w_base"].astype(jnp.float32) + (
        jnp.tanh(xw @ p["w_lora_a"]) @ p["w_lora_b"]
    ).astype(jnp.float32)

    def split(t):
        return t.reshape(*t.shape[:-1], H, hs)

    return split(r), split(k), split(v), split(w), g


def _time_mix_seq(p, x, cfg: ModelConfig, state, shift_in):
    """Sequence form. x: (B, T, D); state: (B, H, K, V); shift_in: (B, D).
    Returns (out, new_state, new_shift)."""
    B, T, D = x.shape
    H = D // cfg.rwkv_head_size
    shifted = jnp.concatenate([shift_in[:, None, :], x[:, :-1]], axis=1)
    r, k, v, w, g = _tmix_rkvwg(p, x, shifted, cfg)
    r = constrain(r, DATA_AXES, None, MODEL_AXIS, None)
    k = constrain(k, DATA_AXES, None, MODEL_AXIS, None)
    v = constrain(v, DATA_AXES, None, MODEL_AXIS, None)
    impl = ops.resolve_impl(cfg.attention_impl, kernel="rwkv6_scan")
    if impl.startswith("pallas"):
        wkv, s_new = ops.rwkv6_scan(r, k, v, w, p["u"], state, impl=impl)
    else:
        wkv, s_new = ref.rwkv6_chunked(r, k, v, w.astype(jnp.float32), p["u"], state)
    wkv = wkv.reshape(B, T, D)
    out = (L.group_rms_norm(wkv, p["ln_x"], H) * g) @ p["wo_t"]
    return constrain(out, DATA_AXES, None, None), s_new, x[:, -1]


def _time_mix_step(p, x, cfg: ModelConfig, state, shift_in):
    """Single-token form. x: (B, D)."""
    B, D = x.shape
    H = D // cfg.rwkv_head_size
    r, k, v, w, g = _tmix_rkvwg(p, x, shift_in, cfg)
    wkv, s_new = ref.rwkv6_decode_step(r, k, v, w, p["u"], state)
    wkv = wkv.reshape(B, D)
    out = (L.group_rms_norm(wkv, p["ln_x"], H) * g) @ p["wo_t"]
    return out, s_new, x


def _channel_mix(p, x, shifted):
    lerped = x + (shifted - x) * p["mu_cm"]
    k = jnp.square(jax.nn.relu(lerped @ p["w_cm_k"]))
    return k @ p["w_cm_v"]


def _layer_seq(cfg, p, x, state, shift_t, shift_c):
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    att, s_new, new_shift_t = _time_mix_seq(p, h, cfg, state, shift_t)
    x = x + att
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    shifted = jnp.concatenate([shift_c[:, None, :], h[:, :-1]], axis=1)
    x = x + _channel_mix(p, h, shifted)
    return x, s_new, new_shift_t, h[:, -1]


def _run_seq(params, cfg: ModelConfig, x, states):
    """x: (B, T, D) embeddings; states: dict of per-layer carries."""

    def body(carry, scanned):
        x = carry
        p, st, sh_t, sh_c = scanned

        fwd = functools.partial(_layer_seq, cfg)
        if cfg.remat:
            fwd = jax.checkpoint(fwd)
        x, s_new, nsh_t, nsh_c = fwd(p, x, st, sh_t, sh_c)
        return x, (s_new, nsh_t, nsh_c)

    x, (s_all, sht_all, shc_all) = jax.lax.scan(
        body, x, (params["layers"], states["wkv"], states["shift_t"], states["shift_c"])
    )
    return x, {"wkv": s_all, "shift_t": sht_all, "shift_c": shc_all,
               "lengths": states["lengths"] + x.shape[1]}


def init_state(cfg: ModelConfig, batch: int) -> Dict[str, jax.Array]:
    D = cfg.d_model
    H = D // cfg.rwkv_head_size
    hs = cfg.rwkv_head_size
    f32 = jnp.float32
    return {
        "wkv": jnp.zeros((cfg.num_layers, batch, H, hs, hs), f32),
        "shift_t": jnp.zeros((cfg.num_layers, batch, D), jnp.dtype(cfg.dtype)),
        "shift_c": jnp.zeros((cfg.num_layers, batch, D), jnp.dtype(cfg.dtype)),
        "lengths": jnp.zeros((batch,), jnp.int32),
    }


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """State stands in for the KV cache; size is O(1) in max_len."""
    return jax.eval_shape(lambda: init_state(cfg, batch))


def loss_fn(params, cfg: ModelConfig, batch: Dict[str, jax.Array]):
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.rms_norm(params["embed"][tokens], params["ln_in"], cfg.norm_eps)
    x = constrain(x, DATA_AXES, None, None)
    x, _ = _run_seq(params, cfg, x, init_state(cfg, B))
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = h @ params["unembed"]
    loss = L.softmax_xent(logits, batch["labels"], batch.get("loss_mask"))
    return loss, {"xent": loss}


def prefill(params, cfg: ModelConfig, batch: Dict[str, jax.Array], max_len: int):
    tokens = batch["tokens"]
    B, T = tokens.shape
    x = L.rms_norm(params["embed"][tokens], params["ln_in"], cfg.norm_eps)
    x = constrain(x, DATA_AXES, None, None)
    x, state = _run_seq(params, cfg, x, init_state(cfg, B))
    h = L.rms_norm(x[:, -1], params["final_norm"], cfg.norm_eps)
    return h @ params["unembed"], state


def decode_step(params, cfg: ModelConfig, batch: Dict[str, jax.Array], cache):
    tok = batch["tokens"]
    x = L.rms_norm(params["embed"][tok], params["ln_in"], cfg.norm_eps)

    def body(carry, scanned):
        x = carry
        p, st, sh_t, sh_c = scanned
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        att, s_new, nsh_t = _time_mix_step(p, h, cfg, st, sh_t)
        x = x + att
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        x = x + _channel_mix(p, h, sh_c)
        return x, (s_new, nsh_t, h)

    x, (s_all, sht, shc) = jax.lax.scan(
        body, x, (params["layers"], cache["wkv"], cache["shift_t"], cache["shift_c"])
    )
    h = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = h @ params["unembed"]
    new_cache = {"wkv": s_all, "shift_t": sht, "shift_c": shc,
                 "lengths": cache["lengths"] + 1}
    return logits, new_cache
