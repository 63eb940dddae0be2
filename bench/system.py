"""The system under test, built from a configuration file.

The model is the program's decoder (``repro.models``) with the sizes the
file states and weights that the benchmark makes itself, on the device, in
one jitted call from the seed. The same model is the memory's encoder
(``ModelEncoder``). Memory is a ``MemForestSystem`` inside a
``DurableMemForest`` whose journal fsyncs every write, and the whole is
served by one ``ServeEngine``.

Three probes stand between the engine and the program; none changes what
is computed:

* ``EncoderProbe`` wraps the encoder: it keeps the texts of every call and
  a seeded sample of the embeddings returned, for the FLOP count and the
  comparison with the reference;
* ``MemoryClock`` wraps the durable memory: it stamps when each ingest and
  query batch returns, which is when a write is acknowledged and a query
  answered, and notes each ingest batch that returned without an fsync of
  the journal (``FsyncProbe`` watches ``os.fsync``);
* ``KernelProbe`` wraps the memory kernels' dispatchers: it keeps a seeded
  sample of their calls (inputs and outputs) for the comparison.
"""
from __future__ import annotations

import os
import random
import time
from typing import Dict, List, Optional


def model_config(cfg: dict):
    from repro.config import ModelConfig

    h = cfg["num_attention_heads"]
    if cfg["hidden_act"] != "silu":
        raise ValueError(f"{cfg['name']}: the served MLP is SwiGLU, "
                         f"not {cfg['hidden_act']}")
    return ModelConfig(
        name=cfg["name"], family="dense",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=h, num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["hidden_size"] // h, d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        max_seq_len=cfg["max_position_embeddings"], mlp_activation="swiglu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["torch_dtype"], param_dtype=cfg["torch_dtype"])


def seed_key(seed: int):
    """A PRNG key from any whole number (the driver's exceed 32 bits)."""
    import jax

    key = jax.random.key(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def make_weights(model, seed: int):
    """Random weights in the served dtype, made on the device by one jitted
    call: norms 1, embeddings N(0, 0.02^2), every matrix N(0, 1/fan_in)."""
    import jax
    import jax.numpy as jnp

    shapes = jax.eval_shape(model.init, jax.random.key(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = getattr(path[-1], "key", "")
            k = jax.random.fold_in(key, i)
            if len(s.shape) == 1 or name.startswith("ln") or name.endswith("norm"):
                out.append(jnp.ones(s.shape, s.dtype))
            elif name == "embed":
                out.append(jax.random.normal(k, s.shape, s.dtype) * jnp.asarray(0.02, s.dtype))
            else:
                scale = jnp.asarray(s.shape[-2] ** -0.5, s.dtype)
                out.append(jax.random.normal(k, s.shape, s.dtype) * scale)
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(make)(seed_key(seed)))


class Sampler:
    """A reservoir of at most ``k`` items over everything offered, drawn by
    a seeded generator; ``make`` is called only for items that are kept."""

    def __init__(self, k: int, rng: random.Random):
        self.k, self.rng, self.n, self.items = k, rng, 0, []

    def offer(self, make):
        self.n += 1
        if len(self.items) < self.k:
            self.items.append(make())
        else:
            j = self.rng.randrange(self.n)
            if j < self.k:
                self.items[j] = make()


class EncoderProbe:
    def __init__(self, inner, rng: random.Random, keep_calls: int = 16):
        self.inner = inner
        self.dim = inner.dim
        self.active = False
        self.calls: List[List[str]] = []         # texts of each call in the window
        self.sample = Sampler(keep_calls, rng)   # (texts, embeddings)

    @property
    def stats(self):
        return self.inner.stats

    def encode(self, texts, *, sequential: bool = False):
        out = self.inner.encode(texts, sequential=sequential)
        if self.active and len(texts):
            texts = list(texts)
            self.calls.append(texts)
            self.sample.offer(lambda: (texts, out))
        return out

    def __getattr__(self, item):
        return getattr(self.inner, item)


class FsyncProbe:
    """Counts the ``os.fsync`` calls that returned for files in ``root``,
    while installed."""

    def __init__(self, root: str):
        self.root = os.path.realpath(root)
        self.count = 0
        self.orig = os.fsync
        os.fsync = self._fsync

    def _fsync(self, fd):
        self.orig(fd)
        try:
            path = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            return
        if os.path.dirname(path) == self.root:
            self.count += 1

    def remove(self):
        os.fsync = self.orig


class MemoryClock:
    def __init__(self, inner, fsyncs: FsyncProbe):
        self.inner = inner
        self.fsyncs = fsyncs
        self.acks: List[tuple] = []          # (return time, sessions)
        self.answers: List[tuple] = []       # (return time, query object ids)
        self.unsynced: List[str] = []        # sessions acked without an fsync

    def ingest_batch(self, sessions, **kw):
        n = self.fsyncs.count
        out = self.inner.ingest_batch(sessions, **kw)
        self.acks.append((time.perf_counter(), list(sessions)))
        if self.fsyncs.count == n:
            self.unsynced += [s.session_id for s in sessions]
        return out

    def query_batch(self, qs, **kw):
        out = self.inner.query_batch(qs, **kw)
        self.answers.append((time.perf_counter(), [id(q) for q in qs]))
        return out

    def __getattr__(self, item):
        return getattr(self.inner, item)


class KernelProbe:
    """Wraps ``repro.kernels.ops.<name>`` for the memory kernels while the
    probe is installed; restores them on ``remove``."""

    NAMES = ("topk_sim", "browse_scores", "tree_refresh")

    def __init__(self, rng: random.Random, keep: int = 4):
        from repro.kernels import ops

        self.ops = ops
        self.active = False
        self.orig = {n: getattr(ops, n) for n in self.NAMES}
        self.samples = {n: Sampler(keep, random.Random(rng.random()))
                        for n in self.NAMES}
        self.fault: Optional[Dict] = None    # tests: {name: fn(out) -> out}
        for n in self.NAMES:
            setattr(ops, n, self._wrap(n))

    def _wrap(self, name):
        fn = self.orig[name]

        def call(*args, **kw):
            out = fn(*args, **kw)
            if self.fault and name in self.fault:
                out = self.fault[name](out)
            if self.active:
                self.samples[name].offer(lambda: (args, dict(kw), out))
            return out
        return call

    def remove(self):
        for n, fn in self.orig.items():
            setattr(self.ops, n, fn)


def build(cfg: dict, params, model, journal_dir: str, rng: random.Random,
          fsyncs: FsyncProbe):
    """(engine, encoder probe, memory clock, durable store)."""
    from repro.config import MemForestConfig
    from repro.core.encoder import ModelEncoder
    from repro.core.journal import DurableMemForest
    from repro.core.memforest import MemForestSystem
    from repro.data.tokenizer import HashTokenizer
    from repro.serving.engine import ServeEngine

    serve = cfg["serve"]
    mcfg = model.cfg
    encoder = EncoderProbe(
        ModelEncoder(mcfg, params, HashTokenizer(mcfg.vocab_size),
                     max_len=serve["encoder_max_tokens"]),
        random.Random(rng.random()))
    system = MemForestSystem(MemForestConfig(embed_dim=mcfg.d_model), encoder)
    store = DurableMemForest(system, journal_dir, fsync=serve["journal_fsync"])
    memory = MemoryClock(store, fsyncs)
    engine = ServeEngine(model, params, memory=memory,
                         max_batch=serve["max_batch"], max_len=serve["max_len"],
                         max_ingest_batch=serve["max_ingest_batch"],
                         max_query_batch=serve["max_query_batch"])
    return engine, encoder, memory, store


def recover(journal_dir: str, dim: int):
    """The memory as ``DurableMemForest.open`` restores it from the run's
    journal directory (snapshot, if any, and the journal replayed). The
    replay embeds with the program's default encoder, the hashing one, and
    not the model: recovery is checked for what was written (facts, their
    sources, turns), and this keeps the replay short."""
    from repro.config import MemForestConfig
    from repro.core.journal import DurableMemForest

    store = DurableMemForest.open(journal_dir, config=MemForestConfig(embed_dim=dim))
    store.close()
    return store.forest
