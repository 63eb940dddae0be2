"""Mamba2 block (SSD) — used by the Zamba2 hybrid.

in_proj -> [z | x | B | C | dt]; causal depthwise conv over [x|B|C];
SSD recurrence (chunked, same math as kernels/mamba2_ssd.py); gated RMSNorm;
out_proj. Decode carries (conv_state, ssd_state).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.kernels import ops, ref
from repro.launch.sharding import DATA_AXES, MODEL_AXIS, constrain
from repro.models import layers as L


def mamba2_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    """(d_inner, num_heads, head_dim, conv_dim)."""
    Din = cfg.d_inner
    P = cfg.ssm_head_dim
    H = Din // P
    conv_dim = Din + 2 * cfg.ssm_state_dim
    return Din, H, P, conv_dim


def mamba2_init(key, cfg: ModelConfig, dtype) -> Dict[str, jax.Array]:
    D = cfg.d_model
    Din, H, P, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state_dim
    ks = jax.random.split(key, 4)
    d_proj = 2 * Din + 2 * N + H  # z, x, B, C, dt
    return {
        "w_in": L.dense_init(ks[0], D, d_proj, dtype),
        "w_out": L.dense_init(ks[1], Din, D, dtype),
        "conv_w": (jax.random.normal(ks[2], (cfg.ssm_conv_width, conv_dim), jnp.float32) * 0.2).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, H)).astype(jnp.float32),
        "dt_bias": jnp.zeros((H,), jnp.float32),
        "D_skip": jnp.ones((H,), jnp.float32),
        "norm_g": jnp.ones((Din,), dtype),
    }


def _split_proj(proj, cfg: ModelConfig):
    Din, H, P, _ = mamba2_dims(cfg)
    N = cfg.ssm_state_dim
    z = proj[..., :Din]
    xbc = proj[..., Din:Din + Din + 2 * N]
    dt = proj[..., Din + Din + 2 * N:]
    return z, xbc, dt


def _causal_conv_seq(xbc, conv_w, conv_b, conv_state):
    """xbc: (B, T, C); conv_state: (B, W-1, C) carried from previous tokens."""
    W = conv_w.shape[0]
    full = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
    out = jnp.zeros_like(xbc)
    for i in range(W):
        out = out + full[:, i:i + xbc.shape[1]] * conv_w[i]
    new_state = full[:, -(W - 1):] if W > 1 else conv_state
    return jax.nn.silu(out + conv_b), new_state


def _causal_conv_step(xbc, conv_w, conv_b, conv_state):
    """xbc: (B, C) single token."""
    W = conv_w.shape[0]
    window = jnp.concatenate([conv_state.astype(xbc.dtype), xbc[:, None]], axis=1)  # (B, W, C)
    out = jnp.einsum("bwc,wc->bc", window, conv_w.astype(xbc.dtype)) + conv_b
    return jax.nn.silu(out), window[:, 1:]


def mamba2_seq(p, x, cfg: ModelConfig, ssd_state, conv_state):
    """x: (B, T, D). Returns (out, new_ssd_state, new_conv_state)."""
    B, T, D = x.shape
    Din, H, P, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state_dim
    proj = x @ p["w_in"]
    proj = constrain(proj, DATA_AXES, None, MODEL_AXIS)
    z, xbc, dt = _split_proj(proj, cfg)
    xbc, new_conv = _causal_conv_seq(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs = xbc[..., :Din].reshape(B, T, H, P)
    Bm = xbc[..., Din:Din + N]
    C = xbc[..., Din + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # (B,T,H)
    A = -jnp.exp(p["A_log"])                                           # (H,)
    impl = ops.resolve_impl(cfg.attention_impl, kernel="mamba2_ssd")
    if impl.startswith("pallas"):
        y, s_new = ops.mamba2_ssd(xs, dt, A, Bm, C, ssd_state, impl=impl)
    else:
        y, s_new = ref.mamba2_ssd_chunked(xs, dt, A, Bm, C, ssd_state)
    y = y + xs * p["D_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, T, Din)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_g"], cfg.norm_eps)
    out = y @ p["w_out"]
    return constrain(out, DATA_AXES, None, None), s_new, new_conv


def mamba2_step(p, x, cfg: ModelConfig, ssd_state, conv_state):
    """x: (B, D) single token."""
    B, D = x.shape
    Din, H, P, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state_dim
    proj = x @ p["w_in"]
    z, xbc, dt = _split_proj(proj, cfg)
    xbc, new_conv = _causal_conv_step(xbc, p["conv_w"], p["conv_b"], conv_state)
    xs = xbc[..., :Din].reshape(B, H, P)
    Bm = xbc[..., Din:Din + N]
    C = xbc[..., Din + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])       # (B,H)
    A = -jnp.exp(p["A_log"])
    y, s_new = ref.mamba2_decode_step(xs, dt, A, Bm, C, ssd_state)
    y = y + xs * p["D_skip"].astype(y.dtype)[None, :, None]
    y = y.reshape(B, Din)
    y = L.rms_norm(y * jax.nn.silu(z), p["norm_g"], cfg.norm_eps)
    return y @ p["w_out"], s_new, new_conv


def state_shapes(cfg: ModelConfig, batch: int):
    Din, H, P, conv_dim = mamba2_dims(cfg)
    N = cfg.ssm_state_dim
    W = cfg.ssm_conv_width
    return (
        (batch, H, P, N),          # ssd state (fp32)
        (batch, W - 1, conv_dim),  # conv state
    )
