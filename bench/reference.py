"""Plain references of what the served path computes. They import nothing
of the program and use only the weights the benchmark made.

* ``forward``: the decoder of the configuration (pre-norm RMSNorm, rotary
  positions by half rotation, causal softmax attention with grouped KV
  heads, SwiGLU MLP, final norm, LM head or tied embedding) in float32 at
  the highest matmul precision, one sequence at a time and layer by layer,
  so that the bf16 weights are widened one layer at a time.
* ``forward(..., fp8=True)``: the same, with every weight and every
  activation that enters a weight matmul rounded to float8 (e4m3, one scale
  per tensor): the next precision below the configuration's bfloat16, which
  is the control that has to fail.
* ``topk_sim``, ``browse_scores``, ``tree_refresh``: the memory kernels'
  semantics in float64 on the host, and their bfloat16 controls.
"""
from __future__ import annotations

import functools
import math

import numpy as np


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
    D = cfg["hidden_size"]
    hd = D // H
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


_MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
               "rms_norm_eps", "rope_theta")


def _items(cfg: dict) -> tuple:
    return tuple((k, cfg[k]) for k in _MODEL_KEYS)


# ---------------------------------------------------------------------------
# memory kernels (host, float64; the control rounds operands to bfloat16)
# ---------------------------------------------------------------------------
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
