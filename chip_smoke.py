#!/usr/bin/env python3
"""On-chip smoke test of the MemForest serve path.

    python chip_smoke.py              # one TPU chip (the default)
    python chip_smoke.py --chips 4    # four TPU chips: sharded memory serve

One chip. phi3-mini at full published width (32 layers, d_model 3072,
random weights from ``--seed``) is both the serving LM and the memory's
``ModelEncoder``. A ``ServeEngine`` with a ``MemForestSystem`` on that
encoder takes a LongMemEval-S-sized history (50 sessions, about 115k tokens)
through ``submit_session``, 64 questions through ``submit_query`` and 8
decode requests through ``submit``, all drained by ``run_until_drained``.
The kernel implementation is the platform's (Pallas on TPU). Checks:

* each served kernel (topk_sim, browse_scores, tree_refresh,
  flash_attention, decode_attention) matches its ``kernels/ref.py`` oracle
  at the served widths, within the tolerance printed beside it;
* the same workload through ``kernel_impl="reference"`` on the same chip
  gives byte-equal answers and evidence;
* every session is ingested, every query answered, every decode finished.

Four chips (``--chips 4``). Only the sharded memory path:
``ServeEngine(sharded=ShardedServeConfig(devices=4))`` against the
``mesh=None`` engine on the same workload (HashingEncoder, a fact index of
several thousand rows), with byte-equal answers and evidence required in
all six browse modes.

Any failed check raises and the script exits non-zero, as it does when no
TPU is present or when ``src/repro`` is not beside it. The last line of
standard output is one JSON object naming the device, and only on success.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

MODES = ("flat", "root-only", "emb", "emb+planner", "llm", "llm+planner")
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class CompileLog:
    """Counts XLA backend compiles (and persistent-cache hits) in this
    process through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.count = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == BACKEND_COMPILE_EVENT:
            self.count += 1
            self.seconds += secs

    def _event(self, event, **_):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1


def require(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# one chip: kernels against their oracles at the served widths
# ---------------------------------------------------------------------------
def check_kernels(cfg, seed: int, *, fact_rows: int, max_len: int,
                  prefill_widths, impl: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops

    keys = iter(jax.random.split(jax.random.key(seed), 32))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def max_err(a, b, rtol=0.0):
        """max(|a - b| - rtol * |b|): the absolute error beyond a
        relative allowance (bf16 outputs differ by whole ulps)."""
        a = jnp.asarray(a, jnp.float32)
        b = jnp.asarray(b, jnp.float32)
        return float(jnp.max(jnp.abs(a - b) - rtol * jnp.abs(b)))

    def report(name, err, tol, rtol=0.0):
        what = f"|pallas - ref| - {rtol:g}*|ref|" if rtol else "|pallas - ref|"
        log(f"kernel {name}: max {what} = {err:.3g} (tol {tol:g})")
        require(np.isfinite(err) and err <= tol, f"{name} exceeds tolerance")

    # Attention outputs are bf16, so 2^-8 * |ref| (their own rounding) is
    # allowed on top of an absolute bound. The oracle is the reference in
    # f32 at HIGHEST matmul precision: at default precision the TPU rounds
    # its operands to bf16 too, and against that a kernel holding its
    # softmax state in bf16 reads the same as this one. The absolute bounds
    # sit about twice over this kernel's readings on v5e; flash's is below
    # what a bf16-state variant reads.
    att_rtol = 2.0 ** -8
    flash_tol, decode_tol = 6e-3, 2e-3

    def oracle(fn, *args):
        with jax.default_matmul_precision("highest"):
            return fn(*(a.astype(jnp.float32) if a.dtype == jnp.bfloat16
                        else a for a in args), impl="reference")

    D = cfg.d_model
    # topk_sim: the fact-index scan (pre-normalized rows, padded capacity)
    q = ops.normalize_rows(normal((32, D)))
    kk = ops.normalize_rows(normal((fact_rows, D)))
    nv = fact_rows - fact_rows // 16
    vp, ip = ops.topk_sim(q, kk, 16, normalize=False, num_valid=nv, impl=impl)
    vr, ir = ops.topk_sim(q, kk, 16, normalize=False, num_valid=nv,
                          impl="reference")
    require(np.array_equal(np.asarray(ip), np.asarray(ir)),
            "topk_sim indices differ from the oracle")
    report(f"topk_sim (32x{fact_rows}x{D}, k=16)", max_err(vp, vr), 1e-5)

    # browse_scores / tree_refresh: one browse level, one flush level (K=8)
    child = ops.normalize_rows(normal((64 * 8, D))).reshape(64, 8, D)
    mask = (jax.random.uniform(next(keys), (64, 8)) < 0.7).astype(jnp.float32)
    qb = ops.normalize_rows(normal((64, D)))
    report(f"browse_scores (64x8x{D})",
           max_err(ops.browse_scores(child, qb, mask, impl=impl),
                   ops.browse_scores(child, qb, mask, impl="reference")), 1e-5)
    report(f"tree_refresh (16x8x{D})",
           max_err(ops.tree_refresh(child[:16], mask[:16], impl=impl),
                   ops.tree_refresh(child[:16], mask[:16], impl="reference")),
           1e-5)

    # attention at the model's heads: prefill widths and the decode cache
    H, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    dt = jnp.dtype(cfg.dtype)
    for S in prefill_widths:
        qa, ka, va = (normal((8, S, h, hd), dt) for h in (H, Hkv, Hkv))
        report(f"flash_attention (8x{S}x{H}x{hd})",
               max_err(ops.attention(qa, ka, va, impl=impl),
                       oracle(ops.attention, qa, ka, va), att_rtol),
               flash_tol, att_rtol)
    qd = normal((8, H, hd), dt)
    kc, vc = (normal((8, max_len, Hkv, hd), dt) for _ in range(2))
    lengths = jax.random.randint(next(keys), (8,), 1, max_len + 1)
    report(f"decode_attention (8x{max_len}x{Hkv}x{hd})",
           max_err(ops.decode_attention(qd, kc, vc, lengths, impl=impl),
                   oracle(ops.decode_attention, qd, kc, vc, lengths),
                   att_rtol),
           decode_tol, att_rtol)


# ---------------------------------------------------------------------------
# one chip: the served path, Pallas against the reference
# ---------------------------------------------------------------------------
def serve_workload(seed: int, *, sessions: int, queries: int,
                   distractor_turns: int):
    from repro.data.synthetic import make_workload

    # LongMemEval-S shape: ~50 sessions per question, a history of ~115k
    # tokens (88k words here), with knowledge-update / temporal questions
    return make_workload(num_entities=16, num_sessions=sessions,
                         transitions_per_entity=4,
                         distractor_turns=distractor_turns,
                         num_queries=queries, seed=seed)


def drive_engine(eng, wl, prompts, max_new_tokens: int):
    """Everything is submitted, then drained by one ``run_until_drained``:
    each engine step admits and decodes, then drains up to 16 sessions and
    32 queries, so queries interleave with the history being written."""
    for s in wl.sessions:
        eng.submit_session(s)
    rids = [eng.submit_query(q) for q in wl.queries]
    for p in prompts:
        eng.submit(p, max_new_tokens=max_new_tokens)
    eng.run_until_drained()
    return [eng.pop_query_result(r) for r in rids]


def serve_phase(cfg, seed: int, *, sessions: int, queries: int,
                distractor_turns: int, decodes: int, max_new_tokens: int,
                max_batch: int, max_len: int, impl: str) -> dict:
    import jax
    import numpy as np

    from repro.config import MemForestConfig
    from repro.core.encoder import ModelEncoder
    from repro.core.memforest import MemForestSystem
    from repro.data.tokenizer import HashTokenizer
    from repro.kernels import ops
    from repro.models import get_model
    from repro.serving.engine import ServeEngine

    require(ops.resolve_impl(cfg.attention_impl) == impl,
            f"attention resolves to {ops.resolve_impl(cfg.attention_impl)}")
    model = get_model(cfg)
    t0 = time.perf_counter()
    # under jit, so dense_init's f32 temporaries never sit beside the
    # bf16 weights they become
    params = jax.block_until_ready(jax.jit(model.init)(jax.random.key(seed)))
    log(f"params: {cfg.name}, {cfg.param_count():,} parameters, "
        f"initialised in {time.perf_counter() - t0:.1f}s")
    tok = HashTokenizer(cfg.vocab_size)
    encoder = ModelEncoder(cfg, params, tok)
    wl = serve_workload(seed, sessions=sessions, queries=queries,
                        distractor_turns=distractor_turns)
    n_tok = sum(len(tok.encode(t.text)) for s in wl.sessions for t in s.turns)
    log(f"workload: {len(wl.sessions)} sessions, {n_tok} history tokens, "
        f"{len(wl.queries)} queries, {decodes} decode requests")
    prompts = [tok.encode("summarize: " + " ".join(
        t.text for t in wl.sessions[i % len(wl.sessions)].turns[:6]))
        for i in range(decodes)]
    # one prompt past max_len / 2, so the engine prefills at width max_len
    # and the shorter prompts beside it must keep their decode slots
    history = tok.encode(" ".join(t.text for s in wl.sessions for t in s.turns))
    prompts[0] = history[:max_len // 2 + 88]
    log(f"decode prompts: {sorted(len(p) for p in prompts)} tokens")
    mem_cfg = MemForestConfig(embed_dim=cfg.d_model)

    t0 = time.perf_counter()
    mf = MemForestSystem(mem_cfg, encoder)
    require(mf.forest.kernel_impl == impl,
            f"memory kernels resolve to {mf.forest.kernel_impl}")
    eng = ServeEngine(model, params, memory=mf, max_batch=max_batch,
                      max_len=max_len)
    got = drive_engine(eng, wl, prompts, max_new_tokens)
    served_s = time.perf_counter() - t0
    m = eng.metrics()
    decoded = sum(len(r.out_tokens) for r in eng.finished)
    log(f"served ({impl}): {m['ingest_sessions']} sessions ingested, "
        f"{m['queries_served']} queries answered, {decoded} tokens decoded "
        f"in {len(eng.finished)} requests, {len(mf.forest.facts)} facts, "
        f"{len(mf.forest.trees)} trees, {mf.forest.flush_calls} flushes, "
        f"{mf.retriever.browse_launches} browse launches, "
        f"{sum(bool(r.answer) for r in got if r is not None)} non-empty answers, "
        f"{served_s:.1f}s wall (compiles included)")
    require(m["ingest_sessions"] == len(wl.sessions), "sessions not ingested")
    require(m["queries_served"] == len(wl.queries), "queries not answered")
    require(all(r is not None for r in got), "a query has no result")
    require(len(eng.finished) == decodes and decoded > 0, "decodes missing")
    require(all(len(r.out_tokens) == max_new_tokens
                or r.out_tokens[-1] == eng.eos_id for r in eng.finished),
            "a decode stopped short of its budget")
    require(mf.forest.flush_calls > 0 and mf.retriever.browse_launches > 0,
            "tree_refresh / browse_scores never ran")
    require(bool(np.isfinite(mf.forest.fact_emb).all()),
            "non-finite fact embeddings")

    # the same workload through the reference kernels, same chip, same
    # encoder: answers and evidence must be byte-equal
    t0 = time.perf_counter()
    ref_mf = MemForestSystem(mem_cfg, encoder, kernel_impl="reference")
    ref_eng = ServeEngine(model, params, memory=ref_mf, max_batch=max_batch,
                          max_len=max_len)
    want = drive_engine(ref_eng, wl, [], max_new_tokens)
    same = sum(a.answer == b.answer and a.evidence == b.evidence
               for a, b in zip(got, want))
    log(f"reference parity: {same}/{len(want)} queries with equal answers "
        f"and evidence ({time.perf_counter() - t0:.1f}s)")
    require(same == len(want), "Pallas and reference answers differ")
    return {"sessions": m["ingest_sessions"], "queries": m["queries_served"],
            "tokens": decoded}


# ---------------------------------------------------------------------------
# four chips: the sharded memory path against mesh=None
# ---------------------------------------------------------------------------
def sharded_workload(seed: int, *, parts: int, sessions: int, queries: int):
    """Several seeded histories with distinct session ids, so the fact index
    reaches thousands of rows (one history tops out near 600 facts)."""
    from repro.data.synthetic import make_workload

    all_sessions, all_queries = [], []
    for i in range(parts):
        wl = make_workload(num_entities=16, num_sessions=sessions,
                           transitions_per_entity=12, num_queries=queries,
                           seed=seed * 1000 + i)
        all_sessions += [dataclasses.replace(s, session_id=f"w{i}-{s.session_id}")
                         for s in wl.sessions]
        all_queries += wl.queries
    return all_sessions, all_queries


def sharded_phase(seed: int, *, devices: int, parts: int, sessions: int,
                  queries: int) -> None:
    import numpy as np

    from repro.config import MemForestConfig
    from repro.configs import get_smoke_config
    from repro.core.memforest import MemForestSystem
    from repro.models import get_model
    from repro.serving.engine import ServeEngine, ShardedServeConfig

    sess, qs = sharded_workload(seed, parts=parts, sessions=sessions,
                                queries=queries)
    # memory-only traffic: the engine's LM lanes stay idle
    model = get_model(get_smoke_config("phi3_mini"))
    results = {}
    for name, sharded in (("mesh=None", None),
                          (f"mesh={devices}", ShardedServeConfig(devices=devices))):
        t0 = time.perf_counter()
        mf = MemForestSystem(MemForestConfig())
        eng = ServeEngine(model, None, memory=mf, sharded=sharded)
        if sharded is not None:
            require(eng.serve_mesh is not None
                    and eng.serve_mesh.devices.size == devices,
                    f"serve mesh is not {devices} devices")
        for s in sess:
            eng.submit_session(s)
        eng.run_until_drained()
        rids = [eng.submit_query(q, mode=MODES[i % len(MODES)])
                for i, q in enumerate(qs)]
        eng.run_until_drained()
        fact_dev, n_facts = mf.forest.fact_index_device()
        spread = len(fact_dev.sharding.device_set)
        results[name] = ([eng.pop_query_result(r) for r in rids], mf)
        log(f"{name}: {len(sess)} sessions, {n_facts} fact rows on {spread} "
            f"device(s), {len(rids)} queries over {len(MODES)} modes, "
            f"{time.perf_counter() - t0:.1f}s")
        require(spread == (devices if sharded is not None else 1),
                f"{name}: fact index spans {spread} devices")
    (base, mf0), (other, mf1) = results.values()
    same = sum(a.answer == b.answer and a.evidence == b.evidence
               for a, b in zip(base, other))
    tree_diff = max(float(np.max(np.abs(t.emb[:t._n] - mf1.forest.trees[k].emb[:t._n])))
                    for k, t in mf0.forest.trees.items())
    log(f"sharded parity: {same}/{len(base)} queries with byte-equal answers "
        f"and evidence; max tree-embedding difference {tree_diff:g}")
    require(same == len(base), "sharded and mesh=None answers differ")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded memory path on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: src/repro is not beside this script", file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    log(f"platform={platform} device_kind={kind} count={len(devs)}")
    if platform != "tpu":
        print("chip_smoke: no TPU found", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: {args.chips} TPU chips needed, {len(devs)} found",
              file=sys.stderr)
        return 1

    from repro.configs import get_config
    from repro.runtime.compile_cache import enable_compile_cache

    log(f"compile cache: {enable_compile_cache()}")
    compiles = CompileLog()
    t0 = time.perf_counter()
    if args.chips == 4:
        sharded_phase(args.seed, devices=4, parts=8, sessions=40, queries=24)
    else:
        cfg = get_config("phi3_mini")
        max_len = 1024
        check_kernels(cfg, args.seed, fact_rows=4096, max_len=max_len,
                      prefill_widths=(64, 128, 1024), impl="pallas")
        counts = serve_phase(cfg, args.seed, sessions=50, queries=64,
                             distractor_turns=100, decodes=8,
                             max_new_tokens=16, max_batch=8, max_len=max_len,
                             impl="pallas")
        log(f"sessions_ingested={counts['sessions']} "
            f"queries_answered={counts['queries']} "
            f"tokens_decoded={counts['tokens']}")
    stats = devs[0].memory_stats() or {}
    log(f"compiles={compiles.count} compile_seconds={compiles.seconds:.1f} "
        f"persistent_cache_hits={compiles.cache_hits} "
        f"wall_seconds={time.perf_counter() - t0:.1f}")
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
