"""Operations and bytes that the served work needs, from its shapes.

The roofline of a kernel is the least time the chip could take for the
work it was given, max(operations / peak FLOP/s, bytes / peak B/s), over
the time the trace shows the kernel running. Counts here are of the work
that is *needed*: real tokens, not padding, and each operand read once. A
kernel that does more (padding, re-reads) reads a lower share; none can
read above 100%.

Counts that depend on the model's architecture (the whole forward, and
which attention calls it makes) live in ``bench/arch/<arch>.py``, built
from the per-call counts here.
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
