"""Embedding encoders for the memory substrate.

HashingEncoder — deterministic, CPU-fast, jitted: token/bigram hashing into a
fixed random projection. Used by benchmarks so write-path timings measure the
*system* (batching, dependency structure), with a realistic per-call forward
cost model.

ModelEncoder — a zoo LM as the builder backbone: tokenize, run the trunk,
mean-pool. Used by examples/serve_memforest.py with a small dense model —
the same code path a production deployment would use with Qwen3 (the paper's
builder).

Both count calls and tokens so benchmarks can report Table-2-style cost.
ModelEncoder also opens an ``encoder.forward`` span per forward (children
``encoder.tokenize`` and ``encoder.device``) carrying its padded shape and,
for an expert model, the rows its expert layers computed.
"""
from __future__ import annotations

import functools
import re
import zlib
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import Observability


def _stable_hash(s: str) -> int:
    """Process-stable string hash (python's hash() is salted per process)."""
    return zlib.crc32(s.encode())

_TOKEN_RE = re.compile(r"[a-z0-9]+")
_HASH_BUCKETS = 8192
# high-frequency glue words contribute almost nothing to a trained embedding
# model's similarity; the hashing stand-in drops them outright.
_STOP = frozenset(
    "a an the of in on at to as is was are were did does do now then it this "
    "that i you he she we they my your his her what where when which who".split()
)


# vocabulary-level id caches (what a trained tokenizer's vocab table is):
# unigram/bigram hashing is pure, and natural-language token vocabularies
# are small, so memoizing ids takes the per-token crc32+encode off the
# write path's host floor. Size-capped: arbitrary alphanumeric tokens (ids,
# hashes) would otherwise grow the dicts without bound in a long-lived
# serving process — on overflow we just stop inserting (misses stay cheap).
_VOCAB_CACHE_MAX = 1 << 16
_UNI_IDS: dict = {}
_BI_IDS: dict = {}


def _tokenize(text: str) -> List[int]:
    toks = [t for t in _TOKEN_RE.findall(text.lower()) if t not in _STOP]
    ids: List[int] = []
    append = ids.append
    prev = None
    for t in toks:
        if prev is not None:
            b = _BI_IDS.get((prev, t))
            if b is None:
                b = _stable_hash(prev + "_" + t) % _HASH_BUCKETS
                if len(_BI_IDS) < _VOCAB_CACHE_MAX:
                    _BI_IDS[(prev, t)] = b
            append(b)
        u = _UNI_IDS.get(t)
        if u is None:
            u = _stable_hash(t) % _HASH_BUCKETS
            if len(_UNI_IDS) < _VOCAB_CACHE_MAX:
                _UNI_IDS[t] = u
        append(u)
        prev = t
    return ids or [0]


@functools.partial(jax.jit, static_argnames=("num_rows",))
def _project(flat_ids: jax.Array, seg: jax.Array, table: jax.Array,
             num_rows: int) -> jax.Array:
    """flat_ids: (N,) bucket ids across all texts, seg: (N,) row index per
    token (sorted; padding tokens carry seg == num_rows) -> (num_rows, dim).

    Computes tanh(counts @ table) in token-gather/segment-sum form: the
    per-row sum of table rows is the same bucket-count contraction without
    materializing either the (B, BUCKETS) dense count matrix or a (B, L)
    padded id matrix — host->device traffic and gather work scale with the
    REAL token count, not with batch x longest-text padding, which keeps
    large mixed-length cross-session ingest batches bandwidth-cheap."""
    contrib = jax.ops.segment_sum(
        table[flat_ids], seg, num_segments=num_rows + 1,
        indices_are_sorted=True)[:num_rows]
    h = jnp.tanh(contrib)
    n = jnp.linalg.norm(h, axis=-1, keepdims=True) + 1e-6
    return h / n


def bucket(n: int, floor: int = 1, cap: Optional[int] = None) -> int:
    """The shape bucket for ``n``: the smallest power of two at or above both
    ``n`` and ``floor``, at most ``cap``. Padding a shape up to its bucket
    keeps the set of jit compiles a long-lived server pays for bounded."""
    b = floor
    while b < n:
        b *= 2
    return b if cap is None else min(b, cap)


class EncoderStats:
    def __init__(self):
        self.calls = 0          # model invocations (a batch = 1 call)
        self.sequential_calls = 0  # calls that were on a dependency chain
        self.tokens = 0
        self.texts = 0

    def reset(self):
        self.__init__()


class HashingEncoder:
    """Deterministic hashing encoder with LLM-like cost accounting."""

    def __init__(self, dim: int = 256, seed: int = 0, max_batch: int = 1024):
        self.dim = dim
        rng = np.random.default_rng(seed)
        self._table = jnp.asarray(
            rng.normal(size=(_HASH_BUCKETS, dim)) / np.sqrt(dim), jnp.float32
        )
        self.stats = EncoderStats()
        self.max_batch = max_batch

    def encode(self, texts: Sequence[str], *, sequential: bool = False) -> np.ndarray:
        """Batched encode. `sequential=True` marks calls that sit on a write
        dependency chain (baselines' state-dependent updates) — they are
        executed one-by-one to reproduce the serialization honestly."""
        if not texts:
            return np.zeros((0, self.dim), np.float32)
        if sequential:
            outs = [self._encode_batch([t]) for t in texts]
            self.stats.sequential_calls += len(texts)
            return np.concatenate(outs, axis=0)
        outs = []
        for i in range(0, len(texts), self.max_batch):
            outs.append(self._encode_batch(texts[i:i + self.max_batch]))
        return np.concatenate(outs, axis=0)

    def _encode_batch(self, texts: Sequence[str]) -> np.ndarray:
        n = len(texts)
        # pad batch rows AND the flat token stream to power-of-two buckets:
        # bounded jit-compile set across the system's lifetime
        cap = bucket(n)
        id_lists = [_tokenize(t) for t in texts]
        ntok = sum(len(ids) for ids in id_lists)
        cap_tok = bucket(ntok, 16)
        flat = np.zeros(cap_tok, np.int32)
        seg = np.full(cap_tok, cap, np.int32)   # padding -> scratch segment
        pos = 0
        for i, ids in enumerate(id_lists):
            flat[pos:pos + len(ids)] = ids
            seg[pos:pos + len(ids)] = i
            pos += len(ids)
        self.stats.calls += 1
        self.stats.tokens += ntok
        self.stats.texts += n
        out = _project(jnp.asarray(flat), jnp.asarray(seg), self._table, cap)
        return np.asarray(out)[:n]

    def encode_one(self, text: str) -> np.ndarray:
        return self.encode([text])[0]


class ModelEncoder:
    """Zoo-LM-backed encoder: trunk forward + masked mean-pool.

    Each forward takes at most ``MAX_ROWS`` texts, and its row count and
    token width pad to power-of-two buckets (as HashingEncoder's do), so a
    long-lived server compiles the trunk for a bounded set of shapes and the
    flash kernel's block always divides the width. Padding rows carry an
    empty mask and are dropped; padding tokens sit after each text, where a
    causal trunk never lets them reach the text's own positions.

    Each forward is an ``encoder.forward`` span (attributes ``texts``,
    ``rows``, ``width``, ``tokens``: real, ``padded_tokens``: rows x
    width; for an expert model also ``expert_rows``, the (token, expert)
    rows its expert layers computed here, summed over layers, and
    ``expert_rows_bound``, the rows of their largest dispatch buffers) with
    children ``encoder.tokenize`` (host tokenizing and padding) and
    ``encoder.device`` (dispatch to the host copy of the result). The
    expert rows also add to the counter ``moe.expert_rows``."""

    # 256 rows x 128 tokens keeps a phi3-mini-width forward's activations to
    # a few GB beside the weights and a serving KV cache on one 16 GB chip
    MAX_ROWS = 256

    def __init__(self, cfg, params, tokenizer, max_len: int = 128):
        from repro.models import get_model  # lazy: avoids cycle
        from repro.models import transformer as T
        from repro.models.layers import expert_buffer_rows

        self.cfg = cfg
        self.params = params
        self.tok = tokenizer
        self.max_len = max_len
        self.dim = cfg.d_model
        self.stats = EncoderStats()
        self.obs = Observability()
        self._expert_rows = self.obs.registry.counter("moe.expert_rows")
        self._buffer_rows = (
            (lambda tokens: cfg.moe_layers * expert_buffer_rows(cfg, tokens))
            if cfg.moe_layers else None)

        def pooled(params, tokens, mask):
            x = params["embed"][tokens]
            h, _, rows = T.trunk(params, cfg, x, jnp.arange(tokens.shape[1])[None, :])
            m = mask[..., None].astype(h.dtype)
            s = jnp.sum(h * m, axis=1) / jnp.maximum(jnp.sum(m, axis=1), 1.0)
            n = jnp.linalg.norm(s.astype(jnp.float32), axis=-1, keepdims=True) + 1e-6
            return (s.astype(jnp.float32) / n), rows

        self._pooled = jax.jit(pooled)

    def encode(self, texts: Sequence[str], *, sequential: bool = False) -> np.ndarray:
        if not texts:
            return np.zeros((0, self.dim), np.float32)
        if sequential:
            self.stats.sequential_calls += len(texts)
            return np.concatenate([self._fwd([t]) for t in texts], axis=0)
        return self._fwd(list(texts))

    def _fwd(self, texts: List[str]) -> np.ndarray:
        if len(texts) > self.MAX_ROWS:
            return np.concatenate(
                [self._fwd(texts[i:i + self.MAX_ROWS])
                 for i in range(0, len(texts), self.MAX_ROWS)], axis=0)
        with self.obs.span("encoder.forward") as sp:
            with self.obs.span("encoder.tokenize"):
                ids = [self.tok.encode(t)[: self.max_len] for t in texts]
                n = len(ids)
                rows = bucket(n, 8)
                L = bucket(max(len(i) for i in ids), 16, self.max_len)
                toks = np.zeros((rows, L), np.int32)
                mask = np.zeros((rows, L), np.float32)
                for i, seq in enumerate(ids):
                    toks[i, : len(seq)] = seq
                    mask[i, : len(seq)] = 1.0
            tokens = int(mask.sum())
            self.stats.calls += 1
            self.stats.tokens += tokens
            self.stats.texts += n
            sp.set(texts=n, rows=rows, width=L, tokens=tokens,
                   padded_tokens=rows * L)
            with self.obs.span("encoder.device"):
                out, expert_rows = jax.device_get(self._pooled(
                    self.params, jnp.asarray(toks), jnp.asarray(mask)))
            if self._buffer_rows is not None:
                self._expert_rows.inc(int(expert_rows))
                sp.set(expert_rows=int(expert_rows),
                       expert_rows_bound=self._buffer_rows(rows * L))
        return np.asarray(out)[:n]

    def encode_one(self, text: str) -> np.ndarray:
        return self.encode([text])[0]
