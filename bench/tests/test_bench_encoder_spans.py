"""The program's own count of the encoder's real tokens (the
``encoder.forward`` spans) agrees with the harness's count of the window's
encoder texts (``RunView.encoder_lengths``), on a whole tiny run."""
import _paths  # noqa: F401  (puts the benchmark and the program on sys.path)

from repro import obs

from loop import Window
from test_bench_run import _cell, _run
from view import RunView


def test_forward_spans_count_the_tokens_the_probe_counts():
    held = {}

    class WindowSink(obs.MemorySink):
        """Keeps the records that close while the probe records calls."""

        def write(self, rec):
            if held["encoder"].active:
                super().write(rec)

    def plant(engine, encoder, memory, kprobe):
        held["encoder"], held["sink"] = encoder, WindowSink()
        obs.enable_tracing(held["sink"])

    try:
        res = _run("backfill", 2**33 + 9, plant=plant)
    finally:
        obs.disable_tracing()
    assert res["correct"], res["checks"]
    view = RunView(_cell("backfill").config, Window(), setup_s=0.0,
                   encoder_calls=held["encoder"].calls)
    real = sum(n for call in view.encoder_lengths() for n in call)
    fwd = held["sink"].spans("encoder.forward")
    assert real > 0 and fwd
    assert sum(r["attrs"]["tokens"] for r in fwd) == real
    assert sum(r["attrs"]["texts"] for r in fwd) == sum(map(len, held["encoder"].calls))
