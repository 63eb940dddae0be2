"""Data pipeline determinism/sharding + serving engine behaviour."""
import jax
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data.pipeline import TokenPipeline
from repro.models import get_model
from repro.serving.engine import BatchedEncoderServer, ServeEngine
from repro.core.encoder import HashingEncoder


def test_pipeline_deterministic_addressing():
    p = TokenPipeline(vocab_size=1000, seq_len=16, global_batch=4, seed=3)
    a = p.batch_at(7)
    b = p.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = p.batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_pipeline_dp_shards_disjoint():
    ps = [TokenPipeline(vocab_size=1000, seq_len=16, global_batch=8,
                        dp_rank=r, dp_size=2, seed=0) for r in range(2)]
    b0, b1 = ps[0].batch_at(0), ps[1].batch_at(0)
    assert b0["tokens"].shape == (4, 16)
    assert not np.array_equal(b0["tokens"], b1["tokens"])


def test_pipeline_labels_are_shifted_tokens():
    p = TokenPipeline(vocab_size=1000, seq_len=16, global_batch=2,
                      corpus=["hello world this is a test " * 20])
    b = p.batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_serve_engine_drains_and_batches():
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=4, max_len=64)
    rng = np.random.default_rng(0)
    n_req = 7
    for i in range(n_req):
        eng.submit(list(rng.integers(3, 400, size=4 + i % 3)), max_new_tokens=3)
    done = eng.run_until_drained()
    assert len(done) == n_req
    assert all(len(r.out_tokens) >= 1 for r in done)
    m = eng.metrics()
    assert m["mean_occupancy"] > 0.5      # continuous batching keeps slots busy
    assert m["decoded_tokens"] >= n_req * 1


def test_continuous_batching_preserves_active_decodes():
    """Admitting new requests mid-flight must not corrupt running decodes:
    outputs for identical prompts must be identical regardless of admission
    interleaving."""
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    prompt = [5, 6, 7, 8]

    eng1 = ServeEngine(model, params, max_batch=2, max_len=32)
    eng1.submit(prompt, max_new_tokens=6)
    out_solo = eng1.run_until_drained()[0].out_tokens

    eng2 = ServeEngine(model, params, max_batch=2, max_len=32)
    eng2.submit(prompt, max_new_tokens=6)
    eng2.step()           # starts decoding request 0
    eng2.submit([9, 10, 11], max_new_tokens=3)  # admitted mid-flight
    out_mixed = next(
        r.out_tokens for r in eng2.run_until_drained() if r.prompt_tokens == prompt
    )
    assert out_solo == out_mixed


def _greedy_exact_width(model, params, prompt, n, max_len):
    """Greedy decode from an unpadded prefill of exactly ``prompt``."""
    import jax.numpy as jnp

    logits, cache = jax.jit(lambda p, b: model.prefill(p, b, max_len))(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)})
    decode = jax.jit(model.decode)
    out = []
    for _ in range(n):
        tok = int(jnp.argmax(logits[0]))
        out.append(tok)
        logits, cache = decode(params, {"tokens": jnp.asarray([tok], jnp.int32)},
                               cache)
    return out


def test_bucketed_prefill_decodes_like_exact_width():
    """A prompt one past half of max_len prefills at width max_len. Padding
    must neither take the decode slots nor change a token: both the long
    prompt and a short one admitted beside it decode exactly as they do from
    exact-width prefills of their own."""
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    max_len = 32
    rng = np.random.default_rng(3)
    long_prompt = [int(t) for t in rng.integers(3, 400, size=max_len // 2 + 1)]
    short_prompt = [5, 6, 7]
    n_long = max_len - len(long_prompt)            # every slot left
    eng = ServeEngine(model, params, max_batch=2, max_len=max_len, eos_id=-1)
    eng.submit(long_prompt, max_new_tokens=n_long)
    eng.submit(short_prompt, max_new_tokens=8)
    done = {tuple(r.prompt_tokens): r.out_tokens
            for r in eng.run_until_drained()}
    assert done[tuple(long_prompt)] == _greedy_exact_width(
        model, params, long_prompt, n_long, max_len)
    assert done[tuple(short_prompt)] == _greedy_exact_width(
        model, params, short_prompt, 8, max_len)


@pytest.mark.parametrize("prompt_len,max_new", [(0, 4), (30, 3), (33, 0)])
def test_submit_rejects_requests_past_max_len(prompt_len, max_new):
    """A request whose prompt plus decode budget overruns the cache raises
    at submit instead of overwriting its own last cache slot."""
    eng = ServeEngine(get_model(get_smoke_config("llama3_8b")), None,
                      max_batch=2, max_len=32)
    with pytest.raises(ValueError):
        eng.submit(list(range(3, 3 + prompt_len)), max_new_tokens=max_new)
    eng.submit(list(range(3, 5)), max_new_tokens=30)   # exactly fits
    assert len(eng.queue) == 1


def test_prefix_cache_reuses_prefill():
    """Re-admitting the same prefix-keyed prompt block must hit the cache,
    skip the prefill launch, and decode identically."""
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32)
    prompts = [[5, 6, 7, 8], [5, 6, 7, 9]]   # shared instruction prefix

    for p in prompts:
        eng.submit(p, max_new_tokens=4, prefix_key="extract")
    out1 = sorted((r.prompt_tokens[-1], r.out_tokens)
                  for r in eng.run_until_drained())
    m1 = eng.metrics()
    assert m1["prefix_misses"] >= 1 and m1["prefix_hits"] == 0

    eng.finished.clear()
    for p in prompts:                          # identical admission recurs
        eng.submit(p, max_new_tokens=4, prefix_key="extract")
    out2 = sorted((r.prompt_tokens[-1], r.out_tokens)
                  for r in eng.run_until_drained())
    m2 = eng.metrics()
    assert m2["prefix_hits"] >= 1
    assert m2["prefills_reused"] >= 1
    assert out1 == out2                        # reuse is output-invariant


def test_query_lane_drains_batched():
    """Queries queued on the engine drain as ONE query_batch per engine
    step and return the same results as calling the memory directly."""
    from repro.config import MemForestConfig
    from repro.core.memforest import MemForestSystem
    from repro.data.synthetic import make_workload

    wl = make_workload(num_entities=4, num_sessions=6,
                       transitions_per_entity=3, num_queries=10, seed=21)
    mf = MemForestSystem(MemForestConfig())
    for s in wl.sessions:
        mf.ingest_session(s)
    want = [r.answer for r in mf.query_batch(wl.queries)]

    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32, memory=mf)
    rids = [eng.submit_query(q) for q in wl.queries]
    eng.submit([5, 6, 7], max_new_tokens=2)    # decode traffic shares the loop
    eng.run_until_drained()

    m = eng.metrics()
    assert m["queries_served"] == len(wl.queries)
    assert m["query_batches"] == 1             # one batched drain, not N
    got = [eng.pop_query_result(r).answer for r in rids]
    assert got == want
    assert not eng.query_results                # consumed: nothing retained


def test_query_lane_requires_memory():
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32)
    with pytest.raises(RuntimeError):
        eng.submit_query(object())


def test_batched_encoder_server_prefix_accounting():
    enc = HashingEncoder(dim=64)
    srv = BatchedEncoderServer(enc)
    out = srv.encode_chunks(["chunk one text", "chunk two text", "chunk three"])
    assert out.shape == (3, 64)
    assert srv.prefix_tokens_saved > 0
    assert enc.stats.calls == 1   # one batched forward, not three


def test_maintenance_lane_defers_flush_off_serve_loop():
    """With a MaintenancePlane attached, ingest drains defer their flush and
    the engine retires refresh work in bounded slices between decode steps —
    answers stay identical to the inline-flush engine."""
    from repro.config import MemForestConfig
    from repro.core.maintenance_plane import MaintenancePlane
    from repro.core.memforest import MemForestSystem
    from repro.data.synthetic import make_workload

    wl = make_workload(num_entities=4, num_sessions=6,
                       transitions_per_entity=3, num_queries=10, seed=22)
    ref = MemForestSystem(MemForestConfig())
    ref.ingest_batch(wl.sessions)
    want = [r.answer for r in ref.query_batch(wl.queries)]

    mf = MemForestSystem(MemForestConfig())
    plane = MaintenancePlane(mf.forest, flush_trees_per_unit=2)
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32, memory=mf,
                      maintenance=plane, maintenance_budget=2)
    for s in wl.sessions:
        eng.submit_session(s)
    eng.submit([5, 6, 7], max_new_tokens=2)    # decode traffic shares the loop
    eng.run_until_drained()                    # lane retires the deferred flush

    m = eng.metrics()
    assert m["maintenance_turns"] > 0          # lane actually ran slices
    assert m["maintenance_pending"] == 0       # drained before exit
    assert not mf.forest.dirty_trees           # readers won't pay the flush

    rids = [eng.submit_query(q) for q in wl.queries]
    eng.run_until_drained()
    got = [eng.pop_query_result(r).answer for r in rids]
    assert got == want
