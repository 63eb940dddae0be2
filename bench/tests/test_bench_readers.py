"""Every metric reader under bench/metrics, whether or not a cell of
BENCHMARK.json names it yet: it reads a number from a run that holds its
inputs, and nothing from a run that holds none."""
import json
import math
import os

import pytest

from _paths import BENCH, FIXTURES

import peaks
import spec
import trace_reduce
from loop import Window
from traffic import Req
from view import RunView, Span

READERS = sorted(f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
                 if f.endswith(".py"))


def _cfg():
    with open(os.path.join(FIXTURES, "tiny.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def full():
    from repro.core.types import Session, Turn

    lm = Req("lm", 0.0, ([5] * 20, 4), done=0.8, tokens=[0.2, 0.4, 0.6, 0.8])
    reqs = [Req("query", 0.1, None, done=0.3), Req("write", 0.2, None, done=0.45), lm]
    sess = Session("b0-s000", [Turn("user", "Bob moved from Boston to Miami in May 2021.", 1.0),
                               Turn("assistant", "Got it, thanks for the update.", 1.1, 1)])
    win = Window(close=1.0, requests=reqs, sessions=[sess],
                 step_ends=[0.2, 0.4, 0.6, 0.8, 1.0],
                 decode_lengths=[[21], [22], [23], [24], []], prefill_lengths=[20])
    spans = [Span("engine.drain.query", 0.1, 0.2, {}),
             Span("journal.fsync", 0.2, 0.01, {}),
             Span("engine.drain.ingest", 0.2, 0.25, {"sessions": 1}),
             Span("forest.flush", 0.4, 0.05, {})]
    return RunView(_cfg(), win, setup_s=12.5,
                   encoder_calls=[["[user] Bob moved from Boston to Miami in May 2021."]],
                   spans=spans, trace=trace_reduce.load(os.path.join(FIXTURES, "probe.xplane.pb")),
                   peaks=peaks.peaks("TPU v5 lite"), arch=spec.arch(_cfg()["arch"]))


@pytest.mark.parametrize("name", READERS)
def test_reads_a_number_where_there_is_one(full, name):
    r = spec.reader(name)
    value = r.read(full)
    assert isinstance(value, float) and math.isfinite(value) and value >= 0, value
    if r.UNIT == "%":
        assert value <= 100.0


@pytest.mark.parametrize("name", READERS)
def test_reads_nothing_where_there_is_nothing(name):
    empty = RunView(_cfg(), Window(close=1.0), setup_s=12.5, encoder_calls=[],
                    arch=spec.arch(_cfg()["arch"]))
    value = spec.reader(name).read(empty)
    assert value == (12.5 if name == "setup_s" else None)
