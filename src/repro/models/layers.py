"""Shared model building blocks: norms, RoPE (plain and YaRN), attention
(standard and multi-head latent), MLP, MoE.

All functions are pure; parameters are plain dicts of jnp arrays. Layer
parameter dicts are stacked along a leading layer dim and scanned
(`lax.scan`) by the model definitions. Activation sharding constraints use
`launch.sharding.constrain`, which no-ops outside a mesh context.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.kernels import ops, ref
from repro.kernels.grouped_matmul import ROW_TILE
from repro.launch.sharding import DATA_AXES, MODEL_AXIS, constrain

Params = Dict[str, jax.Array]


# ---------------------------------------------------------------------------
# initialization helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------
def heads_axis(num_heads: int):
    """`model` if the head count divides evenly over the mesh's model axis,
    else None (replicate — avoids involuntary SPMD remat on GQA kv heads
    narrower than the TP width)."""
    am = jax.sharding.get_abstract_mesh()
    if am.empty or MODEL_AXIS not in am.axis_names:
        return None
    size = dict(am.shape)[MODEL_AXIS]
    return MODEL_AXIS if num_heads % size == 0 else None


def rms_norm(x: jax.Array, gamma: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * gamma.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x: jax.Array, gamma: jax.Array, beta: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * gamma.astype(jnp.float32) + beta.astype(jnp.float32)).astype(x.dtype)


def group_rms_norm(x: jax.Array, gamma: jax.Array, num_heads: int, eps: float = 1e-5) -> jax.Array:
    """Per-head RMS norm over the trailing dim split into heads (RWKV wkv out)."""
    *lead, D = x.shape
    xh = x.reshape(*lead, num_heads, D // num_heads).astype(jnp.float32)
    var = jnp.mean(xh * xh, axis=-1, keepdims=True)
    y = (xh * jax.lax.rsqrt(var + eps)).reshape(*lead, D)
    return (y * gamma.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------
def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D) or (..., H, D) with positions (..., S) or (...,)."""
    D = x.shape[-1]
    half = D // 2
    freqs = jnp.exp(
        -math.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )  # (half,)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(ang)[..., None, :]  # broadcast over heads
    sin = jnp.sin(ang)[..., None, :]
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(seq_len: int, d_model: int) -> jax.Array:
    pos = jnp.arange(seq_len, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d_model // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d_model)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)  # (S, D)


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude correction for a context stretched ``factor`` times."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(dim: int, cfg: ModelConfig) -> np.ndarray:
    """Inverse frequencies of the ``dim / 2`` rotated pairs. With
    ``rope_factor > 1`` (YaRN), frequencies that turn fewer than
    ``yarn_beta_slow`` times over the original context are divided by the
    factor, those that turn more than ``yarn_beta_fast`` times are kept,
    and a linear ramp over the pair index blends the two between."""
    theta = cfg.rope_theta
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if cfg.rope_factor <= 1:
        return extra.astype(np.float32)

    def pair(rotations):      # pair index turning `rotations` times over the context
        return (dim * math.log(cfg.rope_original_max_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(pair(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    return (extra / cfg.rope_factor * ramp + extra * (1.0 - ramp)).astype(np.float32)


def rope_pairs(x: jax.Array, positions: jax.Array, inv_freq: np.ndarray,
               scale: float = 1.0) -> jax.Array:
    """Rotate the adjacent pairs (2i, 2i+1) of x's last dim by the angle
    position * ``inv_freq[i]``, cos and sin times ``scale``. The output holds the
    pairs' first elements, then their second: the published de-interleave
    followed by a half rotation. x: (..., S, H, D) or (..., H, D) with
    positions (..., S) or (...,)."""
    *lead, D = x.shape
    xp = x.astype(jnp.float32).reshape(*lead, D // 2, 2)
    x1, x2 = xp[..., 0], xp[..., 1]
    ang = positions.astype(jnp.float32)[..., None] * jnp.asarray(inv_freq)
    cos = (jnp.cos(ang) * scale)[..., None, :]          # broadcast over heads
    sin = (jnp.sin(ang) * scale)[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, dtype) -> Params:
    if cfg.is_mla:
        return mla_init(key, cfg, dtype)
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, cfg.q_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, cfg.kv_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, cfg.kv_dim, dtype),
        "wo": dense_init(ks[3], cfg.q_dim, cfg.d_model, dtype),
    }


def attention_prefill(
    p: Params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
    *, causal: bool = True, return_kv: bool = False,
):
    """x: (B, S, D). Returns (out, (k, v) if return_kv)."""
    B, S, _ = x.shape
    q = (x @ p["wq"]).reshape(B, S, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
    if cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    kv_ax = heads_axis(cfg.num_kv_heads)
    q = constrain(q, DATA_AXES, None, heads_axis(cfg.num_heads), None)
    k = constrain(k, DATA_AXES, None, kv_ax, None)
    v = constrain(v, DATA_AXES, None, kv_ax, None)
    impl = ops.resolve_impl(cfg.attention_impl)
    if impl == "reference" and S > 1024 and causal:
        o = ref.blockwise_causal_attention(q, k, v)
    else:
        o = ops.attention(q, k, v, causal=causal, impl=impl)
    out = o.reshape(B, S, cfg.q_dim) @ p["wo"]
    out = constrain(out, DATA_AXES, None, None)
    if return_kv:
        return out, (k, v)
    return out


def attention_decode(
    p: Params, x: jax.Array, cfg: ModelConfig,
    k_cache: jax.Array, v_cache: jax.Array, lengths: jax.Array,
):
    """One-token decode. x: (B, D); caches (B, Smax, Hkv, Dh); lengths (B,).
    Returns (out (B, D), new_k_cache, new_v_cache)."""
    B, _ = x.shape
    q = (x @ p["wq"]).reshape(B, cfg.num_heads, cfg.head_dim)
    k = (x @ p["wk"]).reshape(B, cfg.num_kv_heads, cfg.head_dim)
    v = (x @ p["wv"]).reshape(B, cfg.num_kv_heads, cfg.head_dim)
    if cfg.rope_theta > 0:
        q = rope(q, lengths, cfg.rope_theta)
        k = rope(k, lengths, cfg.rope_theta)

    def upd(cache, new, l):
        return jax.lax.dynamic_update_slice(cache, new[None], (l, 0, 0))

    # KV-cache sharding: heads over `model` when they divide the TP width;
    # otherwise shard the SEQUENCE dim (split-KV / flash-decode style — XLA
    # turns the softmax reductions into small per-layer all-reduces, and the
    # multi-GB cache stays fully distributed).
    kv_ax = heads_axis(cfg.num_kv_heads)
    seq_ax = MODEL_AXIS if kv_ax is None else None
    k_cache = jax.vmap(upd)(k_cache, k, lengths)
    v_cache = jax.vmap(upd)(v_cache, v, lengths)
    k_cache = constrain(k_cache, DATA_AXES, seq_ax, kv_ax, None)
    v_cache = constrain(v_cache, DATA_AXES, seq_ax, kv_ax, None)
    o = ops.decode_attention(q, k_cache, v_cache, lengths + 1,
                             impl=ops.resolve_impl(cfg.attention_impl))
    out = o.reshape(B, cfg.q_dim) @ p["wo"]
    return constrain(out, DATA_AXES, None), k_cache, v_cache


def cross_attention(
    p: Params, x: jax.Array, cfg: ModelConfig,
    k: jax.Array, v: jax.Array,
):
    """x: (B, Sq, D) or (B, D); k/v: (B, Skv, Hkv, Dh) precomputed."""
    single = x.ndim == 2
    if single:
        x = x[:, None, :]
    B, Sq, _ = x.shape
    q = (x @ p["wq"]).reshape(B, Sq, cfg.num_heads, cfg.head_dim)
    o = ref.cross_attention_ref(q, k, v)
    out = o.reshape(B, Sq, cfg.q_dim) @ p["wo"]
    return out[:, 0] if single else out


def cross_kv(p: Params, enc_out: jax.Array, cfg: ModelConfig):
    B, Skv, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    v = (enc_out @ p["wv"]).reshape(B, Skv, cfg.num_kv_heads, cfg.head_dim)
    return k, v


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2, no q compression)
# ---------------------------------------------------------------------------
def mla_init(key, cfg: ModelConfig, dtype) -> Params:
    H, R = cfg.num_heads, cfg.kv_lora_rank
    n, r, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, H * (n + r), dtype),
        "wkv_a": dense_init(ks[1], cfg.d_model, R + r, dtype),
        "kv_norm": jnp.ones((R,), dtype),
        "wkv_b": dense_init(ks[2], R, H * (n + dv), dtype),
        "wo": dense_init(ks[3], H * dv, cfg.d_model, dtype),
    }


def mla_softmax_scale(cfg: ModelConfig) -> float:
    """(nope + rope)^-1/2, times YaRN's mscale(all dims) squared."""
    m = yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim) if cfg.yarn_mscale_all_dim else 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def _mla_rope(cfg: ModelConfig):
    """(inverse frequencies, cos/sin scale) of the rotated dims."""
    scale = 1.0
    if cfg.yarn_mscale_all_dim:
        scale = (yarn_mscale(cfg.rope_factor, cfg.yarn_mscale)
                 / yarn_mscale(cfg.rope_factor, cfg.yarn_mscale_all_dim))
    return rope_inv_freq(cfg.qk_rope_head_dim, cfg), scale


def _mla_latent(p: Params, x: jax.Array, cfg: ModelConfig, positions: jax.Array):
    """The cached latent of x (..., D): the normed ``c_kv`` (..., R) and the
    rotated key shared by every head (..., r)."""
    R = cfg.kv_lora_rank
    kv_a = x @ p["wkv_a"]
    c_kv = rms_norm(kv_a[..., :R], p["kv_norm"], cfg.norm_eps)
    freq, scale = _mla_rope(cfg)
    k_pe = rope_pairs(kv_a[..., None, R:], positions, freq, scale)[..., 0, :]
    return c_kv, k_pe


def mla_prefill(p: Params, x: jax.Array, cfg: ModelConfig, positions: jax.Array,
                *, return_kv: bool = False):
    """Decompressed MLA over x (B, S, D): per-head keys (nope from the
    latent, the shared rotated key) and values go through the attention
    kernel, q/k heads nope + rope wide, v heads ``v_head_dim`` wide.
    Returns out (and, with ``return_kv``, the cache rows: c_kv (B, S, R)
    and k_pe (B, S, r))."""
    B, S, _ = x.shape
    H, n, dv = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim
    freq, scale = _mla_rope(cfg)
    q = (x @ p["wq"]).reshape(B, S, H, -1)
    q = jnp.concatenate([q[..., :n], rope_pairs(q[..., n:], positions, freq, scale)], -1)
    c_kv, k_pe = _mla_latent(p, x, cfg, positions)
    kv = (c_kv @ p["wkv_b"]).reshape(B, S, H, n + dv)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_pe[:, :, None], (B, S, H, k_pe.shape[-1]))], -1)
    o = ops.attention(q, k, kv[..., n:], causal=True,
                      impl=ops.resolve_impl(cfg.attention_impl),
                      sm_scale=mla_softmax_scale(cfg))
    out = o.reshape(B, S, H * dv) @ p["wo"]
    if return_kv:
        return out, (c_kv, k_pe)
    return out


def mla_decode(p: Params, x: jax.Array, cfg: ModelConfig, c_cache: jax.Array,
               pe_cache: jax.Array, lengths: jax.Array):
    """One-token MLA against the latent cache, absorbed: ``wkv_b``'s key
    part folds into q, its value part applies after attention, so the
    cache is read as it is stored. x (B, D); c_cache (B, Smax, R); pe_cache
    (B, Smax, r); lengths (B,). Returns (out (B, D), caches)."""
    B, _ = x.shape
    H, n, dv, R = cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank
    freq, scale = _mla_rope(cfg)
    q = (x @ p["wq"]).reshape(B, H, -1)
    q_pe = rope_pairs(q[..., n:], lengths, freq, scale)
    c_kv, k_pe = _mla_latent(p, x, cfg, lengths)

    def upd(cache, new, l):
        return jax.lax.dynamic_update_slice(cache, new[None].astype(cache.dtype), (l, 0))

    c_cache = jax.vmap(upd)(c_cache, c_kv, lengths)
    pe_cache = jax.vmap(upd)(pe_cache, k_pe, lengths)
    wkv_b = p["wkv_b"].reshape(R, H, n + dv)
    f32 = jnp.float32
    q_lat = jnp.einsum("bhn,rhn->bhr", q[..., :n], wkv_b[..., :n],
                       preferred_element_type=f32).astype(c_cache.dtype)
    s = (jnp.einsum("bhr,btr->bht", q_lat, c_cache, preferred_element_type=f32)
         + jnp.einsum("bhe,bte->bht", q_pe, pe_cache, preferred_element_type=f32))
    s = s * mla_softmax_scale(cfg)
    live = jnp.arange(c_cache.shape[1])[None, :] <= lengths[:, None]
    w = jax.nn.softmax(jnp.where(live[:, None, :], s, -1e30), axis=-1)
    o_lat = jnp.einsum("bht,btr->bhr", w.astype(c_cache.dtype), c_cache,
                       preferred_element_type=f32)
    o = jnp.einsum("bhr,rhv->bhv", o_lat.astype(x.dtype), wkv_b[..., n:],
                   preferred_element_type=f32).astype(x.dtype)
    return o.reshape(B, H * dv) @ p["wo"], c_cache, pe_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, dtype, d_ff: Optional[int] = None) -> Params:
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.mlp_activation == "swiglu":
        return {
            "w_gate": dense_init(ks[0], cfg.d_model, F, dtype),
            "w_up": dense_init(ks[1], cfg.d_model, F, dtype),
            "w_down": dense_init(ks[2], F, cfg.d_model, dtype),
        }
    return {
        "wi": dense_init(ks[0], cfg.d_model, F, dtype),
        "wd": dense_init(ks[1], F, cfg.d_model, dtype),
    }


def mlp_block(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.mlp_activation == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])
        h = constrain(h, DATA_AXES, None, MODEL_AXIS) if h.ndim == 3 else h
        out = h @ p["w_down"]
    else:
        h = x @ p["wi"]
        h = jax.nn.gelu(h) if cfg.mlp_activation == "gelu" else jnp.square(jax.nn.relu(h))
        h = constrain(h, DATA_AXES, None, MODEL_AXIS) if h.ndim == 3 else h
        out = h @ p["wd"]
    return constrain(out, DATA_AXES, None, None) if out.ndim == 3 else out


# ---------------------------------------------------------------------------
# MoE block: dropless grouped dispatch on one device, capacity on a mesh
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    """Router over all ``num_experts``; weights of the ``held_experts``
    this device holds; the shared experts, where the model has them."""
    ks = jax.random.split(key, 5)
    E, D, F = cfg.held_experts, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / math.sqrt(D)
    scale_out = 1.0 / math.sqrt(F)
    p = {
        "router": dense_init(ks[0], D, cfg.num_experts, jnp.float32),
        "we_gate": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * scale_in).astype(dtype),
        "we_up": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * scale_in).astype(dtype),
        "we_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * scale_out).astype(dtype),
    }
    if cfg.shared_d_ff:
        p["shared"] = mlp_init(ks[4], cfg, dtype, d_ff=cfg.shared_d_ff)
    return p


def _route(router: jax.Array, xl: jax.Array, cfg: ModelConfig):
    """Router over all ``num_experts`` in float32, greedy top-k: gate
    weights (T, K) f32, expert ids (T, K), and the Switch aux loss."""
    E, K = cfg.num_experts, cfg.experts_per_token
    logits = jnp.dot(xl.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs = jax.nn.softmax(logits, axis=-1)
    gw, gi = jax.lax.top_k(probs, K)
    if cfg.norm_topk_prob:
        gw = gw / (jnp.sum(gw, axis=-1, keepdims=True) + 1e-9)
    me = jnp.mean(probs, axis=0)
    frac = jnp.zeros((E,), jnp.float32).at[gi.reshape(-1)].add(1.0) / (probs.shape[0] * K)
    return gw, gi, E * jnp.sum(frac * me)


def expert_buffer_rows(cfg: ModelConfig, tokens: int) -> int:
    """Rows of the largest dropless dispatch buffer for ``tokens`` tokens:
    every token may send min(top-k, experts held) of its choices here."""
    return _whole_tiles(tokens * min(cfg.experts_per_token, cfg.held_experts))


def _whole_tiles(rows: float) -> int:
    return -(-math.ceil(rows) // ROW_TILE) * ROW_TILE


def expert_buffer_tiers(cfg: ModelConfig, tokens: int) -> Tuple[int, ...]:
    """Rows of the dispatch buffers a forward of ``tokens`` tokens chooses
    from, ascending: where the held experts are a slice of the router's,
    1.25 times the rows an even router would send here, doubled until the
    bound (``expert_buffer_rows``), which is always the last."""
    bound = expert_buffer_rows(cfg, tokens)
    tiers = []
    rows = 1.25 * tokens * cfg.experts_per_token * cfg.held_experts / cfg.num_experts
    while _whole_tiles(rows) < bound:
        tiers.append(_whole_tiles(rows))
        rows *= 2
    return tuple(tiers) + (bound,)


def _moe_dropless(xf, gw, gi, p: Params, cfg: ModelConfig):
    """Every (token, choice) pair whose expert is held here, with no
    capacity: the pairs sorted by held expert into the smallest buffer of
    ``expert_buffer_tiers`` that holds them (a switch on the rows routed,
    so the gathers and the SwiGLU follow the routed rows, not the bound),
    the expert SwiGLU as grouped matmuls over the rows routed, and each
    pair's row gathered back in token order and summed with its gate
    weight (a gather, not a scatter: TPU scatters serialise). Returns (out
    (T, D) f32, rows routed here)."""
    T, D = xf.shape
    K, Eh = gi.shape[1], cfg.held_experts
    local = gi.reshape(-1) - cfg.first_expert
    group = jnp.where((local >= 0) & (local < Eh), local, Eh)   # Eh: not held
    order = jnp.argsort(group, stable=True)
    sizes = jnp.zeros((Eh + 1,), jnp.int32).at[group].add(1)[:Eh]
    routed = jnp.sum(sizes)
    impl = ops.resolve_impl(cfg.attention_impl, "grouped_matmul")

    def experts(m):
        def run():
            # buffer rows past the groups (pairs not held, padding) are
            # computed by no kernel and read by no pair
            n = min(m, T * K)
            rows_in = xf[jnp.pad(order[:n] // K, (0, m - n))]
            h = (jax.nn.silu(ops.grouped_matmul(rows_in, p["we_gate"], sizes, impl=impl))
                 * ops.grouped_matmul(rows_in, p["we_up"], sizes, impl=impl))
            rows_out = ops.grouped_matmul(h, p["we_down"], sizes, impl=impl)
            pos = jnp.full((T * K,), m, jnp.int32).at[order[:n]].set(
                jnp.arange(n, dtype=jnp.int32))
            pos = jnp.where(group < Eh, pos, m)                # m: no row here
            pairs = jnp.take(rows_out, pos, axis=0, mode="fill", fill_value=0)
            return jnp.sum(pairs.reshape(T, K, D).astype(jnp.float32) * gw[..., None],
                           axis=1)
        return run

    tiers = expert_buffer_tiers(cfg, T)
    if len(tiers) == 1:
        return experts(tiers[0])(), routed
    tier = jnp.searchsorted(jnp.asarray(tiers, jnp.int32), routed)
    return jax.lax.switch(tier, [experts(m) for m in tiers]), routed


def _moe_dispatch_compute(xf, gate_w, gate_i, we_gate, we_up, we_down,
                          *, E: int, K: int, C: int, e_lo, E_local: int):
    """Capacity dispatch + expert FFN for experts [e_lo, e_lo + E_local).

    Dispatch avoids the O(T·E·C) one-hot einsum: token ranks within each
    expert come from an argsort over expert assignments, token indices are
    scattered into a compact (E_local·C) buffer, expert inputs are a gather.
    Runs on LOCAL tokens only (see moe_block). Returns (partial out, pairs
    kept here).
    """
    T, D = xf.shape
    eidx = gate_i.reshape(-1)                               # (T*K,)
    tok = jnp.repeat(jnp.arange(T), K)
    w_flat = gate_w.reshape(-1)

    # rank of each (token, choice) within its expert (over ALL E experts so
    # capacity semantics are identical regardless of the expert sharding)
    order = jnp.argsort(eidx, stable=True)
    sorted_e = eidx[order]
    start = jnp.searchsorted(sorted_e, jnp.arange(E), side="left")
    rank_sorted = jnp.arange(T * K) - start[sorted_e]
    rank = jnp.zeros((T * K,), jnp.int32).at[order].set(rank_sorted.astype(jnp.int32))
    keep = (rank < C) & (eidx >= e_lo) & (eidx < e_lo + E_local)
    slot = (eidx - e_lo) * C + rank                         # (T*K,) local slots

    buf = jnp.full((E_local * C,), T, jnp.int32)
    buf = buf.at[jnp.where(keep, slot, E_local * C)].set(
        tok.astype(jnp.int32), mode="drop"
    )
    x_pad = jnp.concatenate([xf, jnp.zeros((1, D), xf.dtype)], axis=0)
    expert_in = x_pad[buf].reshape(E_local, C, D)

    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", expert_in, we_gate)) * jnp.einsum(
        "ecd,edf->ecf", expert_in, we_up
    )
    expert_out = jnp.einsum("ecf,efd->ecd", h, we_down).reshape(E_local * C, D)

    gathered = expert_out[jnp.where(keep, slot, 0)]
    gathered = gathered * (keep.astype(gathered.dtype) * w_flat.astype(gathered.dtype))[:, None]
    return jnp.sum(gathered.reshape(T, K, D), axis=1), jnp.sum(keep)


def moe_block(p: Params, x: jax.Array, cfg: ModelConfig):
    """Token-choice top-k MoE; the router spans all ``num_experts``.

    On one device (no mesh, or experts not divisible over it) the layer
    is dropless: every token's choices among the ``held_experts`` from
    ``first_expert`` are computed (``_moe_dropless``), so a token's output
    does not depend on which tokens share its forward.

    On a mesh, dispatch + expert FFN run under shard_map with a capacity
    per expert, so tokens NEVER leave their data shard: each device gathers
    its local tokens for the experts it owns and the partial outputs are
    combined with ONE psum over `model` per layer — the same collective a
    dense TP layer pays. (The naive global-gather formulation all-gathers
    every token per layer; see EXPERIMENTS.md §Perf for the measured
    difference.)

    The shared experts, where the model has them, are added once.
    Returns (out, aux_loss, expert rows computed).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S

    am = jax.sharding.get_abstract_mesh()
    names = () if am.empty else tuple(am.axis_names)
    if MODEL_AXIS in names and E % dict(am.shape)[MODEL_AXIS] == 0:
        assert cfg.held_experts == E and cfg.first_expert == 0, (
            f"{cfg.name}: the mesh dispatch holds every expert; "
            f"{cfg.held_experts} from {cfg.first_expert} of {E} held")
        tp = dict(am.shape)[MODEL_AXIS]
        dp_axes = tuple(a for a in DATA_AXES if a in names)
        E_local = E // tp
        dp = 1
        for a in dp_axes:
            dp *= dict(am.shape)[a]
        T_local = T // dp
        C = max(int(math.ceil(T_local * K / E * cfg.moe_capacity_factor)), 1)

        fsdp_axes = dp_axes if cfg.moe_fsdp_params else ()

        def local(xb, router, wg, wu, wd):
            # everything token-local happens INSIDE the shard_map: routing,
            # top-k, dispatch — no boundary tensors beyond x itself
            tl = xb.shape[0] * xb.shape[1]
            xl = xb.reshape(tl, D)
            gw, gi, aux = _route(router, xl, cfg)
            if dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)
            e_lo = jax.lax.axis_index(MODEL_AXIS) * E_local
            # FSDP: expert weights arrive sharded over the data axes on dim 1;
            # gather just-in-time (backward = reduce-scatter of the grads)
            if fsdp_axes:
                wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, fsdp_axes, axis=1, tiled=True)
            y, kept = _moe_dispatch_compute(
                xl, gw.astype(xl.dtype), gi, wg, wu, wd,
                E=E, K=K, C=C, e_lo=e_lo, E_local=E_local,
            )
            # combine partials in the activation dtype (not f32)
            y = jax.lax.psum(y.astype(xb.dtype), MODEL_AXIS)
            kept = jax.lax.psum(kept, (MODEL_AXIS,) + dp_axes)
            return y.reshape(xb.shape), aux, kept

        pspec_x = P(dp_axes if dp_axes else None, None, None)
        pspec_w = P(MODEL_AXIS, fsdp_axes if fsdp_axes else None, None)
        y, aux, rows = jax.shard_map(
            local, mesh=am,
            in_specs=(pspec_x, P(), pspec_w, pspec_w, pspec_w),
            out_specs=(pspec_x, P(), P()),
        )(x, p["router"], p["we_gate"], p["we_up"], p["we_down"])
        y = constrain(y, DATA_AXES, None, None)
    else:
        xf = x.reshape(T, D)
        gw, gi, aux = _route(p["router"], xf, cfg)
        y, rows = _moe_dropless(xf, gw, gi, p, cfg)
        y = y.reshape(B, S, D).astype(x.dtype)
    if "shared" in p:
        y = y + mlp_block(p["shared"], x, cfg)
    return y, aux, rows


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def softmax_xent(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """logits (B, S, V), labels (B, S) int32. Mean over valid positions."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    if mask is None:
        return -jnp.mean(ll)
    m = mask.astype(jnp.float32)
    return -jnp.sum(ll * m) / jnp.maximum(jnp.sum(m), 1.0)
