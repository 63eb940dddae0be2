"""A whole run on the CPU at a tiny size: set-up, warm-up, the open-loop
window and the comparison. A sound run is correct; the control (the
reference in the next lower precision in the program's place) fails; and
so does every run with the timed path broken underneath."""
import json
import os
import time

import numpy as np
import pytest

from _paths import FIXTURES

import run
import spec


def _cell(kind):
    with open(os.path.join(FIXTURES, "tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(FIXTURES, f"tiny-{kind}.json")) as f:
        mix = json.load(f)
    names = (["setup_s", "query_p95_ms", "write_ack_p95_ms", "token_gap_p95_ms"]
             if kind == "agent" else ["setup_s", "ingest_tokens_per_s"])
    e2e = [{"name": n, "unit": spec.reader(n).UNIT} for n in names]
    return spec.Cell(name=f"tiny.{kind}", chips=1, config_name="tiny", config=cfg,
                     traffic_name=mix["name"], traffic=mix, end_to_end=e2e)


def _run(kind, seed, **kw):
    return run.run_cell(_cell(kind), seed, 1.5, False, t_start=time.perf_counter(),
                        cache=False, **kw)


def _token(engine, encoder, memory, kprobe):
    """An output token altered where it is produced: the decode's logits."""
    orig = engine._decode

    def decode(params, batch, cache):
        logits, cache = orig(params, batch, cache)
        return logits.at[:, 7].add(1e4), cache
    engine._decode = decode


def _answer(engine, encoder, memory, kprobe):
    """The fact and root scans return their rows in the wrong order."""
    kprobe.fault = {"topk_sim": lambda out: (out[0], out[1][:, ::-1])}


def _embedding(engine, encoder, memory, kprobe):
    inner = encoder.inner.encode
    encoder.inner.encode = lambda texts, **kw: np.roll(inner(texts, **kw), 1, axis=-1)


def _refresh(engine, encoder, memory, kprobe):
    kprobe.fault = {"tree_refresh": lambda out: out * 0.5}


def _journal(engine, encoder, memory, kprobe):
    """Writes acknowledged but never journaled."""
    memory.inner.writer.append = lambda rec: None


def _half(engine, encoder, memory, kprobe):
    """Every ingest batch journaled whole, and half of it applied."""
    system = memory.inner.system
    inner = system.ingest_batch
    system.ingest_batch = lambda sessions, **kw: inner(sessions[:len(sessions) // 2], **kw)


def _no_fsync(engine, encoder, memory, kprobe):
    """The journal appended and flushed to the OS, never fsynced."""
    memory.inner.writer.fsync = False


def _no_flush(engine, encoder, memory, kprobe):
    """Writes applied with their trees left for a flush that never comes."""
    system = memory.inner.system
    inner = system.ingest_batch
    system.ingest_batch = lambda sessions, **kw: inner(sessions, defer_flush=True)


@pytest.fixture(scope="module")
def sound_agent():
    return _run("agent", 2**31 + 7, control=True)


def test_sound_agent_run_is_correct(sound_agent):
    res = sound_agent
    assert res["correct"], res["checks"]
    assert set(res["checks"]) == {"lm_gap", "encoder_gap", "topk_gap", "browse_err",
                                  "refresh_err", "applied_missing", "recovered_missing",
                                  "unflushed", "unsynced_acks"}
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "query_p95_ms", "write_ack_p95_ms",
                                   "token_gap_p95_ms"}
    assert res["notes"]["compiles_in_window"] >= 0
    assert res["checks"]["lm_gap"]["compared"] >= 8


def test_control_fails(sound_agent):
    limits = _cell("agent").config["limits"]
    over = [k for k, v in sound_agent["control_checks"].items()
            if v is not None and v["value"] > limits[k]]
    assert over, sound_agent["control_checks"]


def test_sound_backfill_run_is_correct():
    res = _run("backfill", 5)
    assert res["correct"], res["checks"]
    assert res["metrics"]["ingest_tokens_per_s"]["value"] > 0
    acked = res["notes"]["sessions_acked"]
    assert acked > 0
    for name in ("unflushed", "unsynced_acks"):
        assert res["checks"][name]["compared"] == acked
    for name in ("applied_missing", "recovered_missing"):
        assert res["checks"][name]["compared"] > acked
    assert res["checks"]["encoder_gap"]["compared"] == 16   # 8 texts, 8 index rows


@pytest.mark.parametrize("kind,fault", [("agent", _token), ("agent", _answer),
                                        ("agent", _embedding), ("agent", _journal),
                                        ("backfill", _refresh), ("backfill", _half),
                                        ("backfill", _no_fsync), ("backfill", _no_flush)],
                         ids=["token", "answer", "embedding", "journal", "refresh",
                              "half_batch", "no_fsync", "no_flush"])
def test_a_broken_timed_path_is_not_correct(kind, fault):
    res = _run(kind, 3, plant=fault)
    assert not res["correct"], res["checks"]
    limits = _cell(kind).config["limits"]
    assert any(c["value"] > limits[k] for k, c in res["checks"].items()), res["checks"]


def test_sweep_runs_a_window_at_each_scale():
    import sweep

    out = list(sweep.sweep(_cell("agent"), 11, 1.5, [0.5, 1.0], cache=False))
    assert [o["scale"] for o in out] == [0.5, 1.0]
    for o in out:
        assert o["attempted"] > 0 and o["steps"] > 0
        assert set(o["metrics"]) == {"query_p95_ms", "write_ack_p95_ms", "token_gap_p95_ms"}
    assert out[1]["rates"]["query"] == 2 * out[0]["rates"]["query"]
