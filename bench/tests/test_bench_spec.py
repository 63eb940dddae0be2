"""The benchmark is data: every cell, mix and metric is found by name."""
import json
import os
import re
import shutil

import pytest

from _paths import BENCH, ROOT

import spec

BENCHMARK = spec.load_benchmark()
CELLS = [w["name"] for w in BENCHMARK["workloads"]]
METRICS = BENCHMARK["end_to_end"] + BENCHMARK["per_layer"]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_its_files(cell):
    c = spec.resolve(cell)
    assert c.config["name"] == c.config_name
    assert c.traffic["name"] == c.traffic_name
    assert c.end_to_end and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(spec.reader(m["name"]).read)


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_reader_declares_what_the_benchmark_says(metric):
    m = next(x for x in METRICS if x["name"] == metric)
    r = spec.reader(metric)
    assert (r.UNIT, r.BETTER, r.SOURCE) == (m["unit"], m["better"], m["source"])
    if "moves" in m:
        assert (r.LAYER, r.MOVES) == (m["layer"], m["moves"])


@pytest.mark.parametrize("metric", [m["name"] for m in BENCHMARK["per_layer"]])
def test_moves_names_an_end_to_end_metric_of_all_its_cells(metric):
    m = next(x for x in BENCHMARK["per_layer"] if x["name"] == metric)
    for cell in m.get("workloads", CELLS):
        reported = [e["name"] for e in spec.resolve(cell).end_to_end]
        assert m["moves"] in reported, (metric, cell)


def test_every_cell_reports_setup_another_end_to_end_and_a_layer():
    for cell in CELLS:
        c = spec.resolve(cell)
        names = [m["name"] for m in c.end_to_end]
        assert "setup_s" in names and len(names) >= 2
        assert c.per_layer


def test_names_units_and_bounds_keep_the_contract():
    things = BENCHMARK["configs"] + BENCHMARK["workloads"] + METRICS
    for t in things:
        assert NAME.match(t["name"]), t["name"]
    for m in METRICS:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCHMARK["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert all(w["chips"] == 1 for w in BENCHMARK["workloads"])
    assert 1 <= BENCHMARK["run_seconds"] <= 51
    assert 2 + 14 * 24 <= 43200  # sanity of the check's budget arithmetic
    slots = (43200 - 1200 - 24 * 180) // (2 + 14 * 24)
    assert BENCHMARK["run_seconds"] + 60 <= slots


def test_a_cell_added_as_files_only_is_picked_up(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    before = {p: open(p, "rb").read() for p in (root / "bench").rglob("*") if p.is_file()}
    # a new mix and a new metric, as new files
    mix = json.load(open(root / "bench" / "traffic" / "agent-1k.json"))
    mix["name"] = "agent-1k-burst"
    mix["streams"]["write"]["rate_per_s"] *= 4
    (root / "bench" / "traffic" / "agent-1k-burst.json").write_text(json.dumps(mix))
    (root / "bench" / "metrics" / "write_drain_ms.burst.py").write_text(
        'UNIT = "ms"\nBETTER = "lower"\nSOURCE = "program_span"\n'
        'LAYER = "memory facade / ingest"\nMOVES = "write_ack_p95_ms"\n\n\n'
        'def read(run):\n    d = run.span_durations("engine.drain.ingest")\n'
        '    return 1000 * sum(d) / len(d) if d else None\n')
    # and their entries in BENCHMARK.json
    bench["workloads"].append({"name": "phi3-mini.burst", "config": "phi3-mini",
                               "traffic": "agent-1k-burst", "chips": 1, "why": "bursts"})
    for n in ("query_p95_ms", "write_ack_p95_ms", "token_gap_p95_ms"):
        bench["end_to_end"].append({"name": n, "unit": "ms", "better": "lower", "bound": 0.1,
                                    "source": "host_clock", "workloads": ["phi3-mini.burst"]})
    bench["per_layer"].append({"name": "write_drain_ms.burst", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "memory facade / ingest",
                               "moves": "write_ack_p95_ms",
                               "workloads": ["phi3-mini.burst"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    c = spec.resolve("phi3-mini.burst", root=str(root))
    assert c.traffic["streams"]["write"]["rate_per_s"] == mix["streams"]["write"]["rate_per_s"]
    assert "write_drain_ms.burst" in [m["name"] for m in c.per_layer]
    assert "write_ack_p95_ms" in [m["name"] for m in c.end_to_end]
    assert spec.reader("write_drain_ms.burst", root=str(root)).MOVES == "write_ack_p95_ms"
    # no file that was there has changed
    for p, data in before.items():
        assert p.read_bytes() == data
