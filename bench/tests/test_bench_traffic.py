"""Traffic generation and the metric arithmetic of the window."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from _paths import BENCH, FIXTURES, ROOT

import stats
import traffic
from loop import Window
from traffic import Req
from view import RunView

AGENT = os.path.join(FIXTURES, "tiny-agent.json")
TINY = os.path.join(FIXTURES, "tiny.json")


def _load(path):
    with open(path) as f:
        return json.load(f)


def _schedule(plan):
    return [(r.kind, r.due, r.payload.session_id if r.kind == "write"
             else r.payload.text if r.kind == "query" else tuple(r.payload[0]))
            for r in plan.window]


def test_same_seed_same_schedule():
    mix = _load(AGENT)
    a = traffic.plan(mix, 2**31 + 12345, 10, 512)
    b = traffic.plan(mix, 2**31 + 12345, 10, 512)
    assert _schedule(a) == _schedule(b)
    assert [s.turns[0].text for s in a.preload] == [s.turns[0].text for s in b.preload]


def test_other_seed_same_work_in_another_order():
    mix = _load(AGENT)
    a = traffic.plan(mix, 1, 10, 512)
    b = traffic.plan(mix, 2, 10, 512)
    assert _schedule(a) != _schedule(b)
    for kind in ("query", "write", "lm"):
        ka = [r for r in a.window if r.kind == kind]
        kb = [r for r in b.window if r.kind == kind]
        assert len(ka) == len(kb) == round(mix["streams"][kind]["rate_per_s"] * 10)
        assert all(0 <= r.due < 10 for r in ka + kb)
    def gaps(plan, kind):     # every gap, the one after the last arrival too
        ts = [0.0] + [r.due for r in plan.window if r.kind == kind] + [10.0]
        return sorted(round(y - x, 9) for x, y in zip(ts, ts[1:]))
    for kind in ("query", "write", "lm"):
        assert gaps(a, kind) == gaps(b, kind)
    la = sorted(len(r.payload[0]) for r in a.window if r.kind == "lm")
    lb = sorted(len(r.payload[0]) for r in b.window if r.kind == "lm")
    assert la == lb


def test_every_seed_imports_the_same_tokens():
    """Histories keep their shape across seeds: the seed renames who and
    what, one-to-one and word for word, and the planted statements follow."""
    from tokenizer import HashTokenizer

    mix = _load(os.path.join(FIXTURES, "tiny-backfill.json"))
    tok = HashTokenizer(512)
    seen = []
    for seed in (1, 2**31 + 5):
        plan = traffic.plan(mix, seed, 10, 512)
        sess = [next(plan.backlog) for _ in range(20)]
        seen.append(([[len(tok.encode(t.text)) for t in s.turns] for s in sess],
                     [" ".join(t.text for t in s.turns) for s in sess]))
        for s in sess:
            text = " ".join(t.text for t in s.turns)
            for subj, _attr, value in plan.planted[s.session_id]:
                assert subj in text and value in text
    assert seen[0][0] == seen[1][0]
    assert seen[0][1] != seen[1][1]


def _view(reqs, close):
    w = Window(close=close, requests=reqs)
    return RunView(_load(TINY), w, setup_s=1.0, encoder_calls=[])


def test_p95_of_all_requests_sees_a_planted_stall():
    # 400 queries due every 50 ms over 20 s, answered in 20 ms; one stall of
    # 1.5 s holds back every query due in it
    def reqs(stall):
        out = []
        for i in range(400):
            due = i * 0.05
            done = due + 0.02
            if stall and 10.0 <= due < 11.5:
                done = 11.5 + 0.02
            out.append(Req("query", due, None, done=done))
        return out
    calm = stats.percentile(_view(reqs(False), 20.0).latencies("query"), 95)
    stalled = stats.percentile(_view(reqs(True), 20.0).latencies("query"), 95)
    assert calm == pytest.approx(0.02)
    assert stalled > 0.5
    # the median of per-second p95s does not see it
    lat = _view(reqs(True), 20.0).latencies("query")
    chunks = [stats.percentile(lat[i:i + 20], 95) for i in range(0, 400, 20)]
    assert stats.percentile(chunks, 50) == pytest.approx(0.02)


def test_unanswered_request_counts_at_its_age():
    reqs = [Req("write", 1.0, None, done=1.1), Req("write", 2.0, None)]
    assert _view(reqs, 30.0).latencies("write") == [pytest.approx(0.1), 28.0]
    lm = Req("lm", 0.0, ([1], 4), tokens=[1.0, 1.5, 2.5])
    assert _view([lm], 10.0).token_gaps() == [0.5, 1.0, 7.5]


def _bench(args, cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "bench/run.py"] + args, cwd=cwd,
                          env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_off_the_tpu():
    p = _bench(["--workload", "phi3-mini.backfill", "--seed", "1", "--seconds", "1",
                "--trace", "0"], ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    p = _bench(["--workload", "phi3-mini.backfill", "--seed", "1", "--seconds", "1",
                "--trace", "0"], tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
