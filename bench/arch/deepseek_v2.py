"""DeepSeek-V2 (arXiv:2405.04434), as its published ``config.json`` states
it, without q compression (``q_lora_rank`` null).

The block: pre-norm RMSNorm; multi-head latent attention (MLA): q heads
``qk_nope_head_dim + qk_rope_head_dim`` wide straight from the hidden
state; K and V from a ``kv_lora_rank``-wide latent (RMS-normed) through
``kv_b_proj``, each k head its nope part plus one rotated key of
``qk_rope_head_dim`` shared by every head; v heads ``v_head_dim`` wide.
Rotation by YaRN (``rope_scaling``) over adjacent pairs; the softmax
scale is (nope + rope)^-1/2 times mscale(all dims) squared. The MLP of the
first ``first_k_dense_replace`` layers is a dense SwiGLU of
``intermediate_size``; every later one is the expert layer: softmax over
all routed experts in float32, greedy top ``num_experts_per_tok``, weights
renormalised only with ``norm_topk_prob``, times
``routed_scaling_factor``; SwiGLU experts of ``moe_intermediate_size``;
plus ``n_shared_experts`` shared experts, one SwiGLU of
``n_shared_experts * moe_intermediate_size``. Final norm, untied head.

The cut: ``n_routed_experts`` is the number of experts held here (the
chip's share of expert parallelism); the router keeps the published width
(``reduced.n_routed_experts.published``) and the held experts are the
slice from ``first_expert_held``. What the absent experts would add is
left out, in the program and in the reference alike.

* ``model_config``: the program's ``ModelConfig`` of the file.
* ``forward``, ``embed``: the plain reference, in float32 at the highest
  matmul precision, one sequence at a time and layer by layer,
  decompressed attention, every held expert computed on every token and
  weighted by its own float32 routing. It imports nothing of the program.
  With ``fp8=True`` every weight and every activation that enters a
  weight matmul is rounded to float8 (e4m3, one scale per tensor): the
  control that has to fail.
* ``prefill_flops``, ``decode_flops``: MLA's projections, attention,
  layer 0's MLP, the shared experts, the router, and the held routed
  experts at their expected rows: ``num_experts_per_tok * held / router
  width`` a token (1.5 at 16 of 64 held, top-6), not the rows a forward
  routed. Decode counts the absorbed form.
* ``prefill_attention_calls``: one call of the attention kernel per layer
  at q/k heads of nope + rope and v heads of ``v_head_dim``;
  ``decode_attention_calls``: the absorbed decode per layer, q against the
  latent cache (``kv_lora_rank`` + rope wide) and values ``kv_lora_rank``
  wide.
* ``expert_matmul_calls``: the grouped matmuls of one expert layer, for
  a reader of their share of their roofline.
"""
from __future__ import annotations

import functools
import math
from typing import Iterable, List, Tuple

import numpy as np

_FIXED = {"q_lora_rank": None, "scoring_func": "softmax", "topk_method": "greedy",
          "n_group": 1, "topk_group": 1, "moe_layer_freq": 1, "hidden_act": "silu",
          "attention_bias": False, "routed_scaling_factor": 1}


def router_width(cfg: dict) -> int:
    """Every routed expert of the published model: the router's outputs."""
    cut = cfg.get("reduced", {}).get("n_routed_experts")
    return cut["published"] if cut else cfg["n_routed_experts"]


def model_config(cfg: dict):
    from repro.config import ModelConfig

    for key, want in _FIXED.items():
        if cfg.get(key, want) != want:
            raise ValueError(f"{cfg['name']}: {key} = {cfg[key]!r} is not modelled "
                             f"(only {want!r})")
    rs = cfg["rope_scaling"]
    if rs.get("type") != "yarn":
        raise ValueError(f"{cfg['name']}: rope_scaling {rs.get('type')!r} is not YaRN")
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], num_kv_heads=cfg["num_key_value_heads"],
        head_dim=nope + rope, d_ff=cfg["moe_intermediate_size"],
        vocab_size=cfg["vocab_size"],
        num_experts=router_width(cfg), experts_per_token=cfg["num_experts_per_tok"],
        experts_held=cfg["n_routed_experts"],
        first_expert=cfg.get("first_expert_held", 0),
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"],
        kv_lora_rank=cfg["kv_lora_rank"], qk_nope_head_dim=nope,
        qk_rope_head_dim=rope, v_head_dim=cfg["v_head_dim"],
        rope_theta=float(cfg["rope_theta"]), rope_factor=float(rs["factor"]),
        rope_original_max_len=rs["original_max_position_embeddings"],
        yarn_beta_fast=float(rs["beta_fast"]), yarn_beta_slow=float(rs["beta_slow"]),
        yarn_mscale=float(rs["mscale"]), yarn_mscale_all_dim=float(rs["mscale_all_dim"]),
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


def _yarn(cfg: dict):
    """(inverse frequency of each rotated pair, cos/sin scale, softmax
    scale), from the published YaRN formulas."""
    rs, dim = cfg["rope_scaling"], cfg["qk_rope_head_dim"]
    base, factor = float(cfg["rope_theta"]), float(rs["factor"])
    orig = rs["original_max_position_embeddings"]

    def mscale(m):
        return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0

    def turns_dim(n):
        return dim * math.log(orig / (n * 2 * math.pi)) / (2 * math.log(base))

    lo = max(math.floor(turns_dim(rs["beta_fast"])), 0)
    hi = min(math.ceil(turns_dim(rs["beta_slow"])), dim - 1)
    hi = hi + 0.001 if lo == hi else hi
    i = np.arange(dim // 2, dtype=np.float64)
    plain = base ** (-2 * i / dim)
    mask = 1.0 - np.clip((i - lo) / (hi - lo), 0.0, 1.0)      # 1: keep plain
    inv = plain / factor * (1.0 - mask) + plain * mask
    cs = mscale(rs["mscale"]) / mscale(rs["mscale_all_dim"])
    sm = ((cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
          * mscale(rs["mscale_all_dim"]) ** 2)
    return inv, cs, sm


@functools.lru_cache(maxsize=None)
def _program(cfg_items: tuple, S: int, n_out: int, pooled: bool, fp8: bool):
    import jax
    import jax.numpy as jnp

    cfg = dict(cfg_items)
    cfg["rope_scaling"] = dict(cfg["rope_scaling"])
    H, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope_d, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    K, E = cfg["num_experts_per_tok"], cfg["router_width"]
    lo, held = cfg["first_expert_held"], cfg["n_routed_experts"]
    eps = float(cfg["rms_norm_eps"])
    hi = jax.lax.Precision.HIGHEST
    inv, cs_scale, sm_scale = _yarn(cfg)

    def q8(x):
        return _q8(x.astype(jnp.float32)) if fp8 else x.astype(jnp.float32)

    def mm(a, w):
        return jnp.matmul(q8(a), q8(w), precision=hi)

    def norm(x, g):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
            * g.astype(jnp.float32)

    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * jnp.asarray(inv, jnp.float32)
    cos = (jnp.cos(ang) * cs_scale)[:, None, :]                     # (S, 1, r/2)
    sin = (jnp.sin(ang) * cs_scale)[:, None, :]

    def rope(x):                            # (S, heads, r): pairs (2i, 2i+1)
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)

    causal = jnp.tril(jnp.ones((S, S), bool))
    blk = min(S, 512)

    def attention(x, a):
        q = mm(x, a["wq"]).reshape(S, H, nope + rope_d)
        kv_a = mm(x, a["wkv_a"])
        c = norm(kv_a[:, :R], a["kv_norm"])
        k_pe = rope(kv_a[:, None, R:])                               # (S, 1, r)
        kv = mm(c, a["wkv_b"]).reshape(S, H, nope + dv)
        q_pe = rope(q[..., nope:])
        outs = []
        for s0 in range(0, S, blk):            # query blocks bound memory
            sl = slice(s0, s0 + blk)
            sc = (jnp.einsum("qhd,khd->hqk", q[sl, :, :nope], kv[..., :nope], precision=hi)
                  + jnp.einsum("qhd,kd->hqk", q_pe[sl], k_pe[:, 0], precision=hi))
            sc = jnp.where(causal[sl][None], sc * sm_scale, -jnp.inf)
            w = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", w, kv[..., nope:], precision=hi))
        return mm(jnp.concatenate(outs, 0).reshape(S, H * dv), a["wo"])

    def swiglu(x, m):
        return mm(jax.nn.silu(mm(x, m["w_gate"])) * mm(x, m["w_up"]), m["w_down"])

    def experts(x, m):
        probs = jax.nn.softmax(mm(x, m["router"]), axis=-1)         # (S, E)
        top, idx = jax.lax.top_k(probs, K)
        if cfg["norm_topk_prob"]:
            top = top / jnp.sum(top, -1, keepdims=True)
        top = top * cfg["routed_scaling_factor"]
        gate = jnp.zeros((S, E), jnp.float32).at[
            jnp.arange(S)[:, None], idx].set(top)[:, lo:lo + held]     # (S, held)
        xq, wg, wu, wd = q8(x), q8(m["we_gate"]), q8(m["we_up"]), q8(m["we_down"])
        h = (jax.nn.silu(jnp.einsum("sd,edf->esf", xq, wg, precision=hi))
             * jnp.einsum("sd,edf->esf", xq, wu, precision=hi))
        y = jnp.einsum("esf,efd->esd", q8(h), wd, precision=hi)       # (held, S, D)
        return jnp.einsum("esd,se->sd", y, gate, precision=hi) + swiglu(x, m["shared"])

    def layer(x, p, moe):
        x = x + attention(norm(x, p["ln1"]), p["attn"])
        h = norm(x, p["ln2"])
        return x + (experts(h, p["moe"]) if moe else swiglu(h, p["mlp"])), None

    def run(params, tokens, n, start):
        x = params["embed"][tokens].astype(jnp.float32)
        x, _ = jax.lax.scan(lambda x, p: layer(x, p, False), x, params["dense_layers"])
        x, _ = jax.lax.scan(lambda x, p: layer(x, p, True), x, params["layers"])
        x = norm(x, params["final_norm"])
        if pooled:                                  # masked mean, unit norm
            m = (jnp.arange(S) < n).astype(jnp.float32)[:, None]
            s = jnp.sum(x * m, 0) / jnp.maximum(jnp.sum(m), 1.0)
            return s / (jnp.linalg.norm(s) + 1e-6)
        rows = jax.lax.dynamic_slice_in_dim(x, start, n_out, 0)
        return mm(rows, params["unembed"])

    return jax.jit(run)


def _bucket(n: int, floor: int = 16) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


_MODEL_KEYS = ("num_attention_heads", "kv_lora_rank", "qk_nope_head_dim",
               "qk_rope_head_dim", "v_head_dim", "num_experts_per_tok",
               "n_routed_experts", "norm_topk_prob", "routed_scaling_factor",
               "rms_norm_eps", "rope_theta")


def _items(cfg: dict) -> tuple:
    rs = tuple(sorted(cfg["rope_scaling"].items()))
    return tuple((k, cfg[k]) for k in _MODEL_KEYS) + (
        ("rope_scaling", rs), ("router_width", router_width(cfg)),
        ("first_expert_held", cfg.get("first_expert_held", 0)))


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
def _mla_matmul_flops(cfg: dict) -> float:
    """MLA's projections for one token: q, the latent (and rotated key),
    its decompression into per-head k and v (in decode: the same weights
    absorbed into q and the output), and the output."""
    d, H, R = cfg["hidden_size"], cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return 2.0 * (d * H * (nope + rope) + d * (R + rope) + R * H * (nope + dv) + H * dv * d)


def routed_rows_per_token(cfg: dict) -> float:
    """Expected (token, expert) rows a token sends to the experts held here."""
    return cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / router_width(cfg)


def _mlp_flops(cfg: dict) -> Tuple[float, float]:
    """(layer 0's dense MLP, one expert layer) for one token: the shared
    experts, the router and the held experts at their expected rows."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    dense = 6.0 * d * cfg["intermediate_size"]
    moe = (6.0 * d * f * cfg["n_shared_experts"] + 2.0 * d * router_width(cfg)
           + 6.0 * d * f * routed_rows_per_token(cfg))
    return dense, moe


def _layers(cfg: dict) -> Tuple[int, int]:
    """(dense layers, expert layers)."""
    k = cfg["first_k_dense_replace"]
    return k, cfg["num_hidden_layers"] - k


def _attention(lengths: Iterable[int], cfg: dict, itemsize: int = 2) -> Tuple[float, float]:
    """One layer's causal attention over sequences of ``lengths`` tokens,
    decompressed: QK^T over nope + rope, PV over v, reading q, k, v and
    writing the output once."""
    H, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    dk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 2.0 * H * (dk + dv) * n * (n + 1) / 2
        bytes_ += itemsize * n * H * (2 * dk + 2 * dv)
    return flops, bytes_


def _absorbed(lengths: Iterable[int], cfg: dict, itemsize: int = 2) -> Tuple[float, float]:
    """One layer's one-token absorbed attention for lanes whose caches hold
    ``lengths`` tokens: q (latent + rope wide) against every cached latent
    and rotated key, probabilities against the latents; the cache read
    once, q in and the latent output out."""
    H, R, rope = cfg["num_attention_heads"], cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 2.0 * H * n * (R + rope) + 2.0 * H * n * R
        bytes_ += itemsize * (n * (R + rope) + H * (R + rope) + H * R)
    return flops, bytes_


def prefill_flops(cfg: dict, lengths: Iterable[int], *, logits_per_seq: int) -> float:
    """Forward flops over sequences of ``lengths`` real tokens (causal
    attention over each), plus ``logits_per_seq`` LM-head rows per sequence
    (0 for the encoder's pooled forward). The held experts count at their
    expected rows a token (``routed_rows_per_token``)."""
    lengths = list(lengths)
    n_dense, n_moe = _layers(cfg)
    dense, moe = _mlp_flops(cfg)
    att, _ = _attention(lengths, cfg)
    tokens = sum(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_per_seq * len(lengths)
    return ((n_dense + n_moe) * (_mla_matmul_flops(cfg) * tokens + att)
            + tokens * (n_dense * dense + n_moe * moe) + head)


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """Forward flops of one decode step for lanes whose caches hold
    ``lengths`` tokens (including the one being decoded), LM head included."""
    lengths = list(lengths)
    n_dense, n_moe = _layers(cfg)
    dense, moe = _mlp_flops(cfg)
    att, _ = _absorbed(lengths, cfg)
    lanes = len(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * lanes
    return ((n_dense + n_moe) * (_mla_matmul_flops(cfg) * lanes + att)
            + lanes * (n_dense * dense + n_moe * moe) + head)


def prefill_attention_calls(cfg: dict, lengths: Iterable[int]) -> List[Tuple[float, float]]:
    """(flops, bytes) of each flash-attention call of one forward over
    sequences of ``lengths`` real tokens: one per layer."""
    return [_attention(list(lengths), cfg)] * cfg["num_hidden_layers"]


def decode_attention_calls(cfg: dict, lengths: Iterable[int]) -> List[Tuple[float, float]]:
    """(flops, bytes) of each absorbed decode attention of one step for
    lanes whose caches hold ``lengths`` tokens: one per layer."""
    return [_absorbed(list(lengths), cfg)] * cfg["num_hidden_layers"]


def expert_matmul_calls(cfg: dict, rows_per_layer: float,
                        itemsize: int = 2) -> List[Tuple[float, float]]:
    """(flops, bytes) of each grouped matmul of one expert layer whose held
    experts compute ``rows_per_layer`` (token, expert) rows: gate, up and
    down projections, each reading its rows and every held expert's weights
    once and writing its output once."""
    d, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    r = rows_per_layer
    proj = (2.0 * r * d * f, itemsize * (r * d + held * d * f + r * f))
    return [proj, proj, (2.0 * r * f * d, itemsize * (r * f + held * f * d + r * d))]


def expert_layers(cfg: dict) -> int:
    return _layers(cfg)[1]

