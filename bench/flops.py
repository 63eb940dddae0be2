"""Operations and bytes that the served work needs, from its shapes.

The roofline of a kernel is the least time the chip could take for the
work it was given, max(operations / peak FLOP/s, bytes / peak B/s), over
the time the trace shows the kernel running. Counts here are of the work
that is *needed*: real tokens, not padding, and each operand read once. A
kernel that does more (padding, re-reads) reads a lower share; none can
read above 100%.

The whole-model counts follow the analytic model of the program's
``launch/roofline.py`` (``fwd_flops_per_token``), copied so that the
yardstick stays fixed.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def causal_attention(lengths: Iterable[int], *, heads: int, kv_heads: int,
                     head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One layer's causal self-attention over sequences of ``lengths``
    tokens: QK^T and PV over the causal triangle (2 flops per multiply-add,
    two products), reading q, k and v and writing the output once."""
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 4.0 * heads * head_dim * n * (n + 1) / 2
        bytes_ += itemsize * n * head_dim * (2 * heads + 2 * kv_heads)
    return flops, bytes_


def decode_attention(lengths: Iterable[int], *, heads: int, kv_heads: int,
                     head_dim: int, itemsize: int = 2) -> Tuple[float, float]:
    """One layer's one-token attention for lanes whose caches hold
    ``lengths`` tokens: q against every cached key and value, reading the
    cached K and V once, the query in and the output out."""
    flops = bytes_ = 0.0
    for n in lengths:
        flops += 4.0 * heads * head_dim * n
        bytes_ += itemsize * (2 * n * kv_heads * head_dim + 2 * heads * head_dim)
    return flops, bytes_


def roofline_seconds(flops: float, bytes_: float, peaks: dict) -> Tuple[float, str]:
    """The least time for the work, and which bound sets it."""
    tc = flops / peaks["flops_bf16"]
    tb = bytes_ / peaks["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")


def layer_matmul_flops(cfg: dict) -> float:
    """Projection and MLP flops of one decoder layer for one token."""
    d, h, kvh = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    proj = 2 * d * (h * hd + 2 * kvh * hd) + 2 * h * hd * d
    mlp = 3 * 2 * d * cfg["intermediate_size"]            # SwiGLU: gate, up, down
    return float(proj + mlp)


def model_flops(cfg: dict, lengths: Iterable[int], *, logits_per_seq: int) -> float:
    """Forward flops of the trunk over sequences of ``lengths`` real tokens
    (causal attention over each), plus ``logits_per_seq`` output rows of
    the LM head per sequence (0 for the encoder's pooled forward)."""
    h = cfg["num_attention_heads"]
    att, _ = causal_attention(lengths, heads=h, kv_heads=cfg["num_key_value_heads"],
                              head_dim=cfg["hidden_size"] // h)
    lengths = list(lengths)
    dense = layer_matmul_flops(cfg) * sum(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * logits_per_seq * len(lengths)
    return cfg["num_hidden_layers"] * (dense + att) + head


def decode_flops(cfg: dict, lengths: Iterable[int]) -> float:
    """Forward flops of one decode step for lanes whose caches hold
    ``lengths`` tokens (including the one being decoded), LM head included."""
    h = cfg["num_attention_heads"]
    lengths = list(lengths)
    att, _ = decode_attention(lengths, heads=h, kv_heads=cfg["num_key_value_heads"],
                              head_dim=cfg["hidden_size"] // h)
    dense = layer_matmul_flops(cfg) * len(lengths)
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] * len(lengths)
    return cfg["num_hidden_layers"] * (dense + att) + head
