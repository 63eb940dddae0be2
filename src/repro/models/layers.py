"""Shared model building blocks: norms, RoPE, attention, MLP, MoE.

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
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig
from repro.kernels import ops, ref
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


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------
def attn_init(key, cfg: ModelConfig, dtype) -> Params:
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
# MoE block — dropless-ish capacity dispatch via sort-free rank + gather
# ---------------------------------------------------------------------------
def moe_init(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, 4)
    E, D, F = cfg.num_experts, cfg.d_model, cfg.d_ff
    scale_in = 1.0 / math.sqrt(D)
    scale_out = 1.0 / math.sqrt(F)
    return {
        "router": dense_init(ks[0], D, E, jnp.float32),
        "we_gate": (jax.random.normal(ks[1], (E, D, F), jnp.float32) * scale_in).astype(dtype),
        "we_up": (jax.random.normal(ks[2], (E, D, F), jnp.float32) * scale_in).astype(dtype),
        "we_down": (jax.random.normal(ks[3], (E, F, D), jnp.float32) * scale_out).astype(dtype),
    }


def _moe_dispatch_compute(xf, gate_w, gate_i, we_gate, we_up, we_down,
                          *, E: int, K: int, C: int, e_lo, E_local: int):
    """Capacity dispatch + expert FFN for experts [e_lo, e_lo + E_local).

    Dispatch avoids the O(T·E·C) one-hot einsum: token ranks within each
    expert come from an argsort over expert assignments, token indices are
    scattered into a compact (E_local·C) buffer, expert inputs are a gather.
    Runs on LOCAL tokens only (see moe_block).
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
    return jnp.sum(gathered.reshape(T, K, D), axis=1)       # partial (local experts)


def moe_block(p: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k MoE with per-expert capacity, expert-parallel over
    the `model` axis.

    Routing (cheap) runs replicated; dispatch + expert FFN run under
    shard_map so tokens NEVER leave their data shard: each device gathers its
    local tokens for the experts it owns and the partial outputs are combined
    with ONE psum over `model` per layer — the same collective a dense TP
    layer pays. (The naive global-gather formulation all-gathers every token
    per layer; see EXPERIMENTS.md §Perf for the measured difference.)

    Returns (out, aux_loss).
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    T = B * S

    def gate(xl):
        """Router + top-k + Switch aux loss over local tokens (tl, D)."""
        logits = xl.astype(jnp.float32) @ p["router"].astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gw, gi = jax.lax.top_k(probs, K)
        gw = (gw / (jnp.sum(gw, axis=-1, keepdims=True) + 1e-9)).astype(xl.dtype)
        me = jnp.mean(probs, axis=0)
        frac = jnp.zeros((E,), jnp.float32).at[gi.reshape(-1)].add(1.0) / (probs.shape[0] * K)
        return gw, gi, E * jnp.sum(frac * me)

    am = jax.sharding.get_abstract_mesh()
    names = () if am.empty else tuple(am.axis_names)
    if MODEL_AXIS in names and E % dict(am.shape)[MODEL_AXIS] == 0:
        tp = dict(am.shape)[MODEL_AXIS]
        dp_axes = tuple(a for a in DATA_AXES if a in names)
        E_local = E // tp
        dp = 1
        for a in dp_axes:
            dp *= dict(am.shape)[a]
        T_local = T // dp
        C = max(int(math.ceil(T_local * K / E * cfg.moe_capacity_factor)), 1)

        fsdp_axes = dp_axes if cfg.moe_fsdp_params else ()

        def local(xb, wg, wu, wd):
            # everything token-local happens INSIDE the shard_map: routing,
            # top-k, dispatch — no boundary tensors beyond x itself
            tl = xb.shape[0] * xb.shape[1]
            xl = xb.reshape(tl, D)
            gw, gi, aux = gate(xl)
            if dp_axes:
                aux = jax.lax.pmean(aux, dp_axes)
            e_lo = jax.lax.axis_index(MODEL_AXIS) * E_local
            # FSDP: expert weights arrive sharded over the data axes on dim 1;
            # gather just-in-time (backward = reduce-scatter of the grads)
            if fsdp_axes:
                wg = jax.lax.all_gather(wg, fsdp_axes, axis=1, tiled=True)
                wu = jax.lax.all_gather(wu, fsdp_axes, axis=1, tiled=True)
                wd = jax.lax.all_gather(wd, fsdp_axes, axis=1, tiled=True)
            y = _moe_dispatch_compute(
                xl, gw, gi, wg, wu, wd,
                E=E, K=K, C=C, e_lo=e_lo, E_local=E_local,
            )
            # combine partials in the activation dtype (not f32)
            y = jax.lax.psum(y.astype(xb.dtype), MODEL_AXIS)
            return y.reshape(xb.shape), aux

        pspec_x = P(dp_axes if dp_axes else None, None, None)
        pspec_w = P(MODEL_AXIS, fsdp_axes if fsdp_axes else None, None)
        y, aux = jax.shard_map(
            local, mesh=am,
            in_specs=(pspec_x, pspec_w, pspec_w, pspec_w),
            out_specs=(pspec_x, P()),
        )(x, p["we_gate"], p["we_up"], p["we_down"])
        return constrain(y, DATA_AXES, None, None), aux

    # single-device / non-divisible fallback: same math, all experts local
    gate_w, gate_i, aux = gate(x.reshape(T, D))
    C = max(int(math.ceil(T * K / E * cfg.moe_capacity_factor)), 1)
    y = _moe_dispatch_compute(
        x.reshape(T, D), gate_w, gate_i,
        p["we_gate"], p["we_up"], p["we_down"],
        E=E, K=K, C=C, e_lo=jnp.asarray(0, jnp.int32), E_local=E,
    )
    return y.reshape(B, S, D), aux


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
