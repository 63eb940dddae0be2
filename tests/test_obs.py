"""Observability subsystem (ISSUE 9): histogram quantile accuracy, span
nesting/timing, the no-op backend's cost, trace sink round-trips, and
metric coherence between the legacy ``metrics()`` dicts and the registry
under real mixed engine traffic."""
import json
import random
import time

import pytest

from repro import obs
from repro.obs import (JsonlSink, LatencyHistogram, MemorySink,
                       MetricsRegistry, Observability, percentiles,
                       read_trace)


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with the global tracer disabled."""
    obs.disable_tracing()
    yield
    obs.disable_tracing()


# ---------------------------------------------------------------------------
# histogram accuracy
# ---------------------------------------------------------------------------
def test_histogram_quantiles_match_exact_sort_within_bucket_error():
    """Reported quantiles stay within the documented relative error
    (GROWTH**0.5 - 1 per half-bucket, doubled for rank-vs-interpolation
    slack) of an exact sort across several orders of magnitude."""
    rng = random.Random(17)
    h = LatencyHistogram()
    samples = []
    for _ in range(20000):
        # log-uniform over ~1µs..1s — spans many buckets
        s = 10 ** rng.uniform(-6, 0)
        samples.append(s)
        h.record(s)
    exact = percentiles(samples, (0.50, 0.90, 0.99))
    rel_tol = 2 * (LatencyHistogram.GROWTH ** 0.5 - 1)     # ≈5%
    for q, key in ((0.50, "p50"), (0.90, "p90"), (0.99, "p99")):
        got = h.quantile(q)
        want = exact[key]
        assert abs(got - want) / want <= rel_tol, \
            f"q={q}: histogram {got:.3e} vs exact {want:.3e}"
    assert h.count == len(samples)
    assert h.max == max(samples)
    assert abs(h.sum - sum(samples)) < 1e-6


def test_histogram_edge_cases():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0                  # empty
    h.record(0.0)                                  # below MIN -> bucket 0
    assert h.quantile(0.5) == LatencyHistogram.MIN / 2
    h2 = LatencyHistogram()
    h2.record(1e9)                                 # beyond top bucket: clamped
    assert h2.quantile(0.99) > 0
    s = h2.summary()
    assert s["count"] == 1 and s["max_s"] == 1e9


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    c = reg.counter("serve/x")
    assert reg.counter("serve/x") is c             # stable identity
    c.inc()
    c.inc(2)
    reg.gauge("serve/g").set(7)
    reg.histogram("span/phase").record(0.01)
    snap = reg.snapshot()
    assert snap["serve/x"] == 3
    assert snap["serve/g"] == 7
    assert snap["span/phase/count"] == 1
    assert "phase" in reg.latency_summary()


# ---------------------------------------------------------------------------
# spans: nesting, timing, sinks
# ---------------------------------------------------------------------------
def test_nested_span_timing_and_parenting():
    sink = MemorySink()
    obs.enable_tracing(sink)
    o = Observability()
    with o.span("outer", job="t") as outer:
        time.sleep(0.02)
        with o.span("inner") as inner:
            time.sleep(0.01)
            inner.event("marker", k=1)
    obs.disable_tracing()

    spans = {r["name"]: r for r in sink.spans()}
    assert set(spans) == {"outer", "inner"}
    # child closed first, parented to outer, strictly contained in time
    assert spans["inner"]["parent"] == spans["outer"]["span"]
    assert spans["outer"]["parent"] is None
    assert spans["inner"]["dur_s"] >= 0.01
    assert spans["outer"]["dur_s"] >= spans["inner"]["dur_s"] + 0.02 - 0.005
    assert spans["inner"]["ts"] >= spans["outer"]["ts"]
    assert spans["outer"]["attrs"] == {"job": "t"}
    # the event landed inside the inner span
    (ev,) = sink.events("marker")
    assert ev["span"] == spans["inner"]["span"]
    # span durations also recorded as registry histograms
    assert o.registry.histogram("span/outer").count == 1
    assert o.registry.histogram("span/inner").count == 1


def test_jsonl_sink_round_trip(tmp_path):
    path = str(tmp_path / "trace.jsonl")
    sink = JsonlSink(path)
    obs.enable_tracing(sink)
    o = Observability()
    with o.span("a", n=1):
        o.event("ping")
    obs.disable_tracing()
    sink.close()

    recs = read_trace(path)
    assert [r["kind"] for r in recs] == ["event", "span"]
    assert [r["name"] for r in recs] == ["ping", "a"]  # span written at close
    assert recs[1]["attrs"] == {"n": 1}
    # every line is valid standalone JSON
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_noop_backend_is_shared_and_cheap():
    o = Observability()
    s1 = o.span("hot")
    s2 = o.span("hot2", attr=1)
    assert s1 is s2 is obs.NULL_SPAN           # no allocation while disabled
    with s1 as s:
        s.set(x=1).event("y")                  # all no-ops

    iters = 50_000
    t0 = time.perf_counter()
    for _ in range(iters):
        with o.span("hot"):
            pass
    per_call = (time.perf_counter() - t0) / iters
    assert per_call < 5e-6, f"disabled span cost {per_call * 1e6:.2f}µs"


def test_disabled_tracer_emits_nothing():
    sink = MemorySink()
    o = Observability()
    with o.span("quiet"):
        o.event("nope")
    assert sink.records == []
    assert o.registry.latency_summary() == {}  # no span histograms recorded


# ---------------------------------------------------------------------------
# metric coherence under mixed engine traffic
# ---------------------------------------------------------------------------
def test_engine_metrics_cohere_with_registry_under_mixed_traffic():
    """The legacy metrics() dict and the raw registry can never disagree —
    they are the same counters — and a traced engine run populates the
    per-phase span histograms for every active phase."""
    import jax
    import numpy as np

    from repro.config import MemForestConfig
    from repro.configs import get_smoke_config
    from repro.core.maintenance_plane import MaintenancePlane
    from repro.core.memforest import MemForestSystem
    from repro.data.synthetic import make_workload
    from repro.models import get_model
    from repro.serving.engine import ServeEngine

    wl = make_workload(num_entities=4, num_sessions=6,
                       transitions_per_entity=3, num_queries=8, seed=31)
    mf = MemForestSystem(MemForestConfig())
    plane = MaintenancePlane(mf.forest, flush_trees_per_unit=2)
    cfg = get_smoke_config("llama3_8b")
    model = get_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, max_batch=2, max_len=32, memory=mf,
                      maintenance=plane, maintenance_budget=2)

    sink = MemorySink()
    obs.enable_tracing(sink)
    rng = np.random.default_rng(3)
    for s in wl.sessions:
        eng.submit_session(s)
    eng.submit(list(rng.integers(3, 400, size=4)), max_new_tokens=3)
    eng.run_until_drained()        # maintenance lane retires deferred flushes
    rids = [eng.submit_query(q) for q in wl.queries]
    eng.run_until_drained()
    obs.disable_tracing()
    for r in rids:
        assert eng.pop_query_result(r) is not None

    m = eng.metrics()
    snap = eng.obs.registry.snapshot()
    pairs = [
        ("decode_steps", "serve/decode_steps"),
        ("decoded_tokens", "serve/decoded_tokens"),
        ("prefills", "serve/prefills"),
        ("ingest_batches", "serve/ingest_batches"),
        ("ingest_sessions", "serve/ingest_sessions"),
        ("query_batches", "serve/query_batches"),
        ("queries_served", "serve/queries_served"),
        ("maintenance_turns", "serve/maintenance_turns"),
    ]
    for legacy, reg_name in pairs:
        assert m[legacy] == snap[reg_name], (legacy, reg_name)
    # attribute back-compat reads the same counters
    assert eng.ingest_sessions == m["ingest_sessions"] == len(wl.sessions)
    assert eng.queries_served == len(wl.queries)
    # plane counters flow into the same dict from its own registry
    assert m["maintenance_units"] == plane.units_run
    assert m["maintenance_pending"] == 0
    # wait histograms saw every request
    assert snap["serve/ingest_wait_s/count"] == len(wl.sessions)
    assert snap["serve/query_wait_s/count"] == len(wl.queries)
    assert m["query_wait_p99_s"] >= m["query_wait_p50_s"] >= 0

    # the traced run populated per-phase histograms + the trace itself
    phases = eng.latency_summary()
    for want in ("engine.step", "engine.admit", "engine.decode",
                 "engine.drain.ingest", "engine.drain.query",
                 "engine.drain.maintenance"):
        assert want in phases and phases[want]["count"] > 0, want
    # the plane's own spans land in ITS registry (flush slices ran)
    assert "maintenance.flush_slice" in plane.obs.registry.latency_summary()
    step_spans = sink.spans("engine.step")
    assert len(step_spans) >= snap["serve/decode_steps"]  # idle steps traced too
    # drains nest under engine.step in the trace
    step_ids = {r["span"] for r in step_spans}
    for r in sink.spans("engine.drain.ingest"):
        assert r["parent"] in step_ids


def test_forest_flush_and_journal_spans_share_system_registry(tmp_path):
    """Forest flush + journal append/checkpoint spans land in the owning
    system's registry, and the JSONL trace nests fsync under append."""
    from repro.core.journal import DurableMemForest
    from repro.data.synthetic import make_workload

    sink = MemorySink()
    obs.enable_tracing(sink)
    store = DurableMemForest.open(str(tmp_path / "d"))
    wl = make_workload(num_entities=3, num_sessions=4,
                       transitions_per_entity=2, num_queries=2, seed=9)
    store.ingest_batch(wl.sessions, idempotency_key="k1")
    store.checkpoint()
    obs.disable_tracing()

    reg = store.obs.registry
    assert store.forest.obs is store.obs       # one registry per system
    summ = reg.latency_summary()
    for want in ("journal.append", "journal.fsync", "journal.checkpoint",
                 "forest.flush"):
        assert want in summ, want
    assert reg.counter("journal/appends").value == store.writer.appends
    assert reg.counter("journal/commits").value == store.ops_applied
    assert reg.counter("journal/checkpoints").value == 1
    append_ids = {r["span"] for r in sink.spans("journal.append")}
    for r in sink.spans("journal.fsync"):
        assert r["parent"] in append_ids
    store.close()


# ---------------------------------------------------------------------------
# thread-safety under the background maintenance plane (ISSUE 10 satellite):
# counters/histograms are written from the serve thread AND the plane's
# worker at once, and snapshots race lazy registration
# ---------------------------------------------------------------------------
def test_counter_and_histogram_are_thread_safe_under_contention():
    """`value += n` is a read-modify-write the GIL does not make atomic;
    with a tiny switch interval the unlocked version loses increments
    within a handful of runs. The locked primitives must count exactly."""
    import sys
    import threading

    reg = MetricsRegistry()
    c = reg.counter("stress/c")
    h = reg.histogram("stress/h")
    n_threads, n_iters = 8, 2000

    def worker(tid):
        for i in range(n_iters):
            c.inc()
            h.record(1e-4 * (1 + (i + tid) % 7))

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=worker, args=(t,))
              for t in range(n_threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sys.setswitchinterval(old)

    assert c.value == n_threads * n_iters
    assert h.count == n_threads * n_iters
    assert h.summary()["count"] == n_threads * n_iters
    # bucket totals agree with count: no torn record() left them skewed
    assert sum(h._b) == h.count


def test_registry_get_or_create_race_yields_one_instance():
    """Concurrent get-or-create of the SAME name from many threads must
    converge on one object — otherwise two components increment different
    counters under one name and the snapshot under-reports."""
    import sys
    import threading

    reg = MetricsRegistry()
    got = []

    def worker():
        for i in range(300):
            got.append((i, reg.counter(f"race/c{i}")))
            reg.histogram(f"race/h{i}")
            reg.gauge(f"race/g{i}")

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        ts = [threading.Thread(target=worker) for _ in range(6)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
    finally:
        sys.setswitchinterval(old)

    by_name = {}
    for i, cnt in got:
        by_name.setdefault(i, set()).add(id(cnt))
    assert all(len(ids) == 1 for ids in by_name.values())


def test_snapshot_during_concurrent_registration_never_raises():
    """snapshot()/counters()/latency_summary() iterate the registry dicts
    while the maintenance worker is still registering new metrics lazily;
    unlocked iteration dies with 'dict changed size during iteration'."""
    import threading

    reg = MetricsRegistry()
    stop = threading.Event()
    errors = []

    def registrar():
        # fresh counter/gauge names keep the dicts growing (the iteration
        # race needs live insertions); histograms cycle over a small set so
        # snapshot()'s per-histogram summary cost stays bounded
        i = 0
        while not stop.is_set() and i < 20000:
            reg.counter(f"reg/c{i}").inc()
            reg.histogram(f"span/h{i % 32}").record(1e-3)
            reg.gauge(f"reg/g{i}").set(i)
            i += 1

    def snapshotter():
        try:
            for _ in range(150):
                reg.snapshot()
                reg.counters()
                reg.histograms()
                reg.latency_summary()
        except RuntimeError as e:          # pragma: no cover - the bug
            errors.append(e)

    reg_t = threading.Thread(target=registrar)
    snap_t = threading.Thread(target=snapshotter)
    reg_t.start()
    snap_t.start()
    snap_t.join()
    stop.set()
    reg_t.join()
    assert not errors


def test_tracer_event_races_disable_without_crashing():
    """Tracer.disable() nulls the sink from one thread while another is
    mid `_emit_event`; the emit path must capture the sink once (no
    check-then-act on self.sink)."""
    import threading

    from repro.obs.trace import Tracer

    errors = []

    def hammer(tr):
        try:
            for _ in range(300):
                tr.event("e", {"k": 1})
        except AttributeError as e:        # pragma: no cover - the bug
            errors.append(e)

    for _ in range(30):
        tr = Tracer()
        tr.enable(MemorySink())
        t = threading.Thread(target=hammer, args=(tr,))
        t.start()
        tr.disable()
        t.join()
    assert not errors


# ---------------------------------------------------------------------------
# spans on the profiler's clock: each span enters a TraceMe of its name
# ---------------------------------------------------------------------------
def _profiled_spans(tmp_path, tracing: bool):
    """Nested spans around a jitted call under ``jax.profiler.trace``;
    returns {name: (start_ns, end_ns)} of the span names found on any line
    of a ``/host:`` plane (its python line is ``python`` or ``python3``)."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    f = jax.jit(lambda x: x * 2.0 + 1.0)
    x = jnp.ones(16)
    f(x).block_until_ready()
    if tracing:
        obs.enable_tracing(MemorySink())
    o = Observability()
    with jax.profiler.trace(str(tmp_path)):
        with o.span("obs.test.parent", n=3):
            with o.span("obs.test.child"):
                f(x).block_until_ready()
    obs.disable_tracing()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("obs.test."):
                    found[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    return found


def test_spans_reach_the_profiler_trace_nested(tmp_path):
    found = _profiled_spans(tmp_path, tracing=True)
    assert set(found) == {"obs.test.parent", "obs.test.child"}, found
    (pa, pb), (ca, cb) = found["obs.test.parent"], found["obs.test.child"]
    assert pa <= ca < cb <= pb


def test_disabled_spans_leave_no_profiler_event(tmp_path):
    assert _profiled_spans(tmp_path, tracing=False) == {}


class _RecordingAnnotation:
    """Stands in for ``TraceAnnotation``: records each enter and exit."""
    log = []

    def __init__(self, *args, **kwargs):
        self.args, self.kwargs = args, kwargs

    def __enter__(self):
        self.log.append(("enter", self.args, self.kwargs))

    def __exit__(self, *exc):
        self.log.append(("exit", self.args, self.kwargs))


def _recording_tracer():
    tr = obs.Tracer().enable(MemorySink())
    tr.annotation = _RecordingAnnotation
    _RecordingAnnotation.log = []
    return tr, Observability(tracer=tr)


def test_span_enters_a_traceme_of_its_name_only():
    tr, o = _recording_tracer()
    with o.span("outer", n=3):
        with o.span("inner", k="v"):
            pass
    assert _RecordingAnnotation.log == [
        ("enter", ("outer",), {}), ("enter", ("inner",), {}),
        ("exit", ("inner",), {}), ("exit", ("outer",), {})]


def test_traceme_is_left_when_the_body_raises():
    tr, o = _recording_tracer()
    with pytest.raises(ValueError):
        with o.span("outer"):
            raise ValueError("boom")
    assert [e[0] for e in _RecordingAnnotation.log] == ["enter", "exit"]
    assert tr.sink.spans("outer")            # the span was still recorded


def test_enable_resolves_the_profiler_annotation():
    from jax.profiler import TraceAnnotation

    tr = obs.Tracer()
    assert tr.annotation is None
    assert tr.enable().annotation is TraceAnnotation
    assert obs.Tracer(enabled=True).annotation is TraceAnnotation


def test_obs_imports_and_runs_disabled_without_jax():
    import os
    import subprocess
    import sys

    code = ("import sys; sys.modules['jax'] = None\n"
            "from repro import obs\n"
            "o = obs.Observability()\n"
            "with o.span('x') as sp: sp.set(n=1)\n"
            "assert sp is obs.NULL_SPAN\n")
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src)
    r = subprocess.run([sys.executable, "-c", code], env=env, capture_output=True,
                       text=True, timeout=60)
    assert r.returncode == 0, r.stderr
