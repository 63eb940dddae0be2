"""The dense decoder: pre-norm RMSNorm, rotary positions by half rotation,
causal softmax attention with grouped KV heads, SwiGLU MLP, final norm, LM
head or tied embedding. Heads are ``head_dim`` wide where the file states
it, else ``hidden_size / num_attention_heads``.

* ``model_config``: the program's ``ModelConfig`` of the file.
* ``forward``, ``embed``: the plain reference, in float32 at the highest
  matmul precision, one sequence at a time and layer by layer, so that the
  bf16 weights are widened one layer at a time. It imports nothing of the
  program and uses only the weights the benchmark made. With ``fp8=True``
  every weight and every activation that enters a weight matmul is rounded
  to float8 (e4m3, one scale per tensor): the next precision below the
  configuration's bfloat16, which is the control that has to fail.
* ``prefill_flops``, ``decode_flops``: the analytic model of the program's
  ``launch/roofline.py`` (``fwd_flops_per_token``), copied so that the
  yardstick stays fixed.
* ``prefill_attention_calls``, ``decode_attention_calls``: one call of the
  attention kernel per layer, every layer alike.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, List, Tuple

import numpy as np

import flops


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def model_config(cfg: dict):
    from repro.config import ModelConfig

    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: the served MLP is SwiGLU, "
                         f"not {cfg['hidden_act']}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=head_dim(cfg), d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"], mlp_activation="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"])


# ---------------------------------------------------------------------------
# plain reference
# ---------------------------------------------------------------------------
def _q8(x):
    """Round to float8 e4m3 with one scale for the tensor."""
    import jax.numpy as jnp

    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@functools.lru_cache(maxsize=None)
def _program(cfg_items: tuple, S: int, n_out: int, pooled: bool, fp8: bool):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    hi = jax.lax.Precision.HIGHEST

    def mm(a, w):
        a, w = a.astype(jnp.float32), w.astype(jnp.float32)
        if fp8:
            a, w = _q8(a), _q8(w)
        return jnp.matmul(a, w, precision=hi)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * g.astype(jnp.float32)

    half = hd // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]

    def rope(x):                                   # (S, heads, hd)
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))
    blk = min(S, 512)

    def layer(x, p):
        a = p["attn"]
        h = norm(x, p["ln1"])
        q = rope(mm(h, a["wq"]).reshape(S, H, hd))
        k = rope(mm(h, a["wk"]).reshape(S, Hkv, hd))
        v = mm(h, a["wv"]).reshape(S, Hkv, hd)
        k = jnp.repeat(k, H // Hkv, axis=1)
        v = jnp.repeat(v, H // Hkv, axis=1)
        outs = []
        for s0 in range(0, S, blk):                # query blocks bound memory
            sc = jnp.einsum("qhd,khd->hqk", q[s0:s0 + blk], k, precision=hi)
            sc = sc / math.sqrt(hd)
            sc = jnp.where(causal[s0:s0 + blk][None], sc, -jnp.inf)
            w = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", w, v, precision=hi))
        o = jnp.concatenate(outs, 0).reshape(S, H * hd)
        x = x + mm(o, a["wo"])
        h = norm(x, p["ln2"])
        m = p["mlp"]
        y = mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]), m["w_down"])
        return x + y, None

    def run(params, tokens, n, start):
        x = params["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(layer, x, params["layers"])
        x = norm(x, params["final_norm"])
        if pooled:                                  # masked mean, unit norm
            m = (jnp.arange(S) < n).astype(jnp.float32)[:, None]
            s = jnp.sum(x * m, 0) / jnp.maximum(jnp.sum(m), 1.0)
            return s / (jnp.linalg.norm(s) + 1e-6)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, 0)
        head = params["embed"].T if "unembed" not in params else params["unembed"]
        return mm(rows, head)

    return jax.jit(run)


def _bucket(n: int, floor: int = 16) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


_MODEL_KEYS = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
               "rope_theta")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _MODEL_KEYS) + (("head_dim", head_dim(cfg)),)


def forward(params, cfg: dict, tokens, start: int, n_out: int, *, fp8=False):
    """Logits (n_out, vocab) at positions start .. start + n_out - 1 of
    ``tokens``, as float32 numpy. Positions past ``tokens`` are padding,
    which causal attention keeps out of every real position."""
    import jax.numpy as jnp

    rows = _bucket(n_out, 1)
    S = _bucket(max(len(tokens), start + rows))
    toks = np.zeros(S, np.int32)
    toks[:len(tokens)] = tokens
    fn = _program(_items(cfg), S, rows, False, fp8)
    out = fn(params, jnp.asarray(toks), len(tokens), start)
    return np.asarray(out, np.float32)[:n_out]


def embed(params, cfg: dict, tokens, width: int, *, fp8=False):
    """The encoder's unit-norm mean-pooled embedding of ``tokens``."""
    import jax.numpy as jnp

    toks = np.zeros(width, np.int32)
    toks[:len(tokens)] = tokens
    fn = _program(_items(cfg), width, 1, True, fp8)
    return np.asarray(fn(params, jnp.asarray(toks), len(tokens), 0), np.float64)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------
def _heads(cfg: dict) -> dict:
    return {"heads": cfg["num_attention_heads"],
            "kv_heads": cfg["num_key_value_heads"], "head_dim": head_dim(cfg)}


def layer_matmul_flops(cfg: dict) -> float:
    """Projection and MLP flops of one decoder layer for one token."""
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = head_dim(cfg)
    proj = 2 * d * (h * hd + 2 * kvh * hd) + 2 * h * hd * d
    mlp = 3 * 2 * d * cfg["intermediate_size"]            # SwiGLU: gate, up, down
    return float(proj + mlp)


def prefill_flops(cfg: dict, lengths: Iterable[int], *, logits_per_seq: int) -> float:
    """Forward flops of the trunk over sequences of ``lengths`` real tokens
    (causal attention over each), plus ``logits_per_seq`` output rows of
    the LM head per sequence (0 for the encoder's pooled forward)."""
    lengths = list(lengths)
    att, _ = flops.causal_attention(lengths, **_heads(cfg))
    dense = layer_matmul_flops(cfg) * sum(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_per_seq * len(lengths)
    return cfg["num_hidden_layers"] * (dense + att) + head


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """Forward flops of one decode step for lanes whose caches hold
    ``lengths`` tokens (including the one being decoded), LM head included."""
    lengths = list(lengths)
    att, _ = flops.decode_attention(lengths, **_heads(cfg))
    dense = layer_matmul_flops(cfg) * len(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * len(lengths)
    return cfg["num_hidden_layers"] * (dense + att) + head


def prefill_attention_calls(cfg: dict, lengths: Iterable[int]) -> List[Tuple[float, float]]:
    """(flops, bytes) of each flash-attention call of one forward over
    sequences of ``lengths`` real tokens: one per layer."""
    return [flops.causal_attention(lengths, **_heads(cfg))] * cfg["num_hidden_layers"]


def decode_attention_calls(cfg: dict, lengths: Iterable[int]) -> List[Tuple[float, float]]:
    """(flops, bytes) of each decode-attention call of one step for lanes
    whose caches hold ``lengths`` tokens: one per layer."""
    return [flops.decode_attention(lengths, **_heads(cfg))] * cfg["num_hidden_layers"]
