"""The write path's spans: a tiny ModelEncoder behind a DurableMemForest,
driven through ServeEngine's ingest lane. Each forward is an
``encoder.forward`` span carrying its padded shape, with ``encoder.tokenize``
and ``encoder.device`` inside; the batch's phases are ``ingest.extract``,
``ingest.canonicalize`` and ``ingest.route`` inside ``engine.drain.ingest``;
the refresh kernel's wait is ``forest.tree_refresh.device``."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.config import MemForestConfig
from repro.configs import get_smoke_config
from repro.core.encoder import ModelEncoder, bucket
from repro.core.journal import DurableMemForest
from repro.core.memforest import MemForestSystem
from repro.data.synthetic import make_workload
from repro.data.tokenizer import HashTokenizer
from repro.models import get_model
from repro.serving.engine import ServeEngine


@pytest.fixture(autouse=True)
def _tracing_off():
    obs.disable_tracing()
    yield
    obs.disable_tracing()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """(engine, encoder, store, sessions): 8 sessions queued, nothing run."""
    cfg = get_smoke_config("llama3_8b").replace(
        d_model=32, num_heads=2, num_kv_heads=2, head_dim=16, num_layers=1)
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    encoder = ModelEncoder(cfg, params, HashTokenizer(cfg.vocab_size), max_len=32)
    system = MemForestSystem(MemForestConfig(embed_dim=cfg.d_model), encoder)
    store = DurableMemForest(system, str(tmp_path_factory.mktemp("journal")))
    engine = ServeEngine(model, params, memory=store, max_batch=2, max_len=64,
                         max_ingest_batch=4)
    wl = make_workload(num_entities=3, num_sessions=8, transitions_per_entity=2,
                       num_queries=1, seed=13)
    yield engine, encoder, store, wl.sessions
    store.close()


def _ingest(engine, sessions):
    for s in sessions:
        engine.submit_session(s)
    engine.run_until_drained()


@pytest.fixture(scope="module")
def traced(served):
    """The first half of the sessions ingested with tracing on: the sink's
    span records by id, and the real tokens the encoder counted meanwhile."""
    engine, encoder, store, sessions = served
    sink = obs.MemorySink()
    tok0 = encoder.stats.tokens
    obs.enable_tracing(sink)
    _ingest(engine, sessions[:4])
    obs.disable_tracing()
    return {r["span"]: r for r in sink.spans()}, encoder.stats.tokens - tok0


def _named(spans, name):
    return [r for r in spans.values() if r["name"] == name]


def _parent(spans, r):
    return spans[r["parent"]]["name"] if r["parent"] is not None else None


def test_write_path_spans_nest(traced):
    spans, _ = traced
    chain = [("encoder.tokenize", "encoder.forward"), ("encoder.device", "encoder.forward"),
             ("encoder.forward", "ingest.extract"), ("ingest.extract", "engine.drain.ingest"),
             ("ingest.canonicalize", "engine.drain.ingest"),
             ("ingest.route", "engine.drain.ingest"),
             ("forest.tree_refresh.device", "forest.tree_refresh")]
    for child, parent in chain:
        found = _named(spans, child)
        assert found, child
        assert all(_parent(spans, r) == parent for r in found), (child, parent)
    # a child lies inside its parent in time
    for r in _named(spans, "encoder.device"):
        p = spans[r["parent"]]
        assert p["ts"] <= r["ts"] and r["ts"] + r["dur_s"] <= p["ts"] + p["dur_s"]


def test_forward_spans_carry_the_padded_shape(traced):
    spans, tokens = traced
    fwd = _named(spans, "encoder.forward")
    for r in fwd:
        a = r["attrs"]
        assert a["padded_tokens"] == a["rows"] * a["width"] >= a["tokens"] > 0
        assert a["rows"] >= a["texts"] > 0
    assert sum(r["attrs"]["tokens"] for r in fwd) == tokens
    # every text the batch embedded went through the forwards under it
    for ex in _named(spans, "ingest.extract"):
        kids = [r for r in fwd if r["parent"] == ex["span"]]
        assert ex["attrs"]["texts"] == sum(r["attrs"]["texts"] for r in kids) > 0


def test_ingest_phase_spans_carry_their_counts(served, traced):
    spans, _ = traced
    store = served[2]
    canon = sorted(_named(spans, "ingest.canonicalize"), key=lambda r: r["ts"])
    route = sorted(_named(spans, "ingest.route"), key=lambda r: r["ts"])
    assert len(canon) == len(route) == len(_named(spans, "engine.drain.ingest"))
    for c, r in zip(canon, route):
        assert c["attrs"]["candidates"] >= c["attrs"]["facts"] == r["attrs"]["facts"]
        assert r["attrs"]["cells"] > 0
    assert route[-1]["attrs"]["scenes"] == len(store.forest.scene_counts) > 0


def test_tracing_off_records_nothing(served, traced):
    engine, encoder, store, sessions = served
    before = (encoder.obs.registry.latency_summary(), store.obs.registry.latency_summary())
    tok0 = encoder.stats.tokens
    _ingest(engine, sessions[4:])
    assert encoder.stats.tokens > tok0          # the forwards ran
    after = (encoder.obs.registry.latency_summary(), store.obs.registry.latency_summary())
    assert after == before                      # no span recorded a duration


def test_forward_shape_is_the_encoders_bucket(served, traced):
    spans, _ = traced
    encoder = served[1]
    for r in _named(spans, "encoder.forward"):
        a = r["attrs"]
        assert a["rows"] == bucket(a["texts"], 8) <= encoder.MAX_ROWS
        assert a["width"] == bucket(a["width"], 16, encoder.max_len)
        assert a["tokens"] <= a["texts"] * a["width"]


def test_forwards_split_at_max_rows(served):
    encoder = served[1]
    texts = [f"fact number {i} about the move" for i in range(20)]
    sink = obs.MemorySink()
    calls0 = encoder.stats.calls
    encoder.MAX_ROWS = 8
    try:
        obs.enable_tracing(sink)
        encoder.encode(texts)
        obs.disable_tracing()
    finally:
        del encoder.MAX_ROWS
    fwd = sink.spans("encoder.forward")
    assert [r["attrs"]["texts"] for r in fwd] == [8, 8, 4]
    assert encoder.stats.calls - calls0 == len(fwd)


def test_spans_leave_the_embeddings_as_they_are(served):
    encoder = served[1]
    texts = ["Bob moved from Boston to Miami in May 2021.", "Got it."]
    off = encoder.encode(texts)
    obs.enable_tracing(obs.MemorySink())
    on = encoder.encode(texts)
    obs.disable_tracing()
    np.testing.assert_array_equal(on, off)


def test_ingest_phase_spans_with_the_hashing_encoder():
    system = MemForestSystem(MemForestConfig())
    wl = make_workload(num_entities=3, num_sessions=4, transitions_per_entity=2,
                       num_queries=1, seed=5)
    sink = obs.MemorySink()
    obs.enable_tracing(sink)
    system.ingest_batch(wl.sessions)
    obs.disable_tracing()
    for name in ("ingest.extract", "ingest.canonicalize", "ingest.route"):
        assert len(sink.spans(name)) == 1, name
    assert sink.spans("ingest.extract")[0]["attrs"]["texts"] > 0
    assert not sink.spans("encoder.forward")   # the hashing encoder opens none
