#!/usr/bin/env python3
"""Find the knee of an open-loop mix: one process sets up a cell once, then
runs one window at each scale of the mix's stream rates, lowest first.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> --scales 0.5,1,2

For each scale it prints one JSON line: the end-to-end metrics, requests
attempted and failed, and whether a queue grew over the window (the p95
latency of the requests due in its last fifth against its first fifth,
and how long after the last due request the window closed). The knee is
the highest scale at which no queue grows; a cell runs at about four
fifths of it, written into its traffic file as fixed rates.
"""
from __future__ import annotations

import argparse
import copy
import json
import random
import shutil
import sys
import tempfile
import time

import run


def _grows(view, kind: str, seconds: float):
    from stats import percentile

    reqs = view.requests(kind)
    if len(reqs) < 10:
        return None
    close = view.window.close
    lat = lambda rs: percentile([(r.done if r.done is not None else close) - r.due
                                 for r in rs], 95)
    first = [r for r in reqs if r.due < seconds / 5]
    last = [r for r in reqs if r.due >= seconds * 4 / 5]
    return {"p95_first_fifth_s": lat(first), "p95_last_fifth_s": lat(last)}


def sweep(cell, seed: int, seconds: float, scales, *, cache: bool = True):
    """One result per scale, lowest first, from one set-up of ``cell``."""
    import loop
    import system
    import traffic
    from repro.models import get_model
    from view import RunView

    if cache:
        run.enable_compile_cache()
    cfg, mix = cell.config, cell.traffic
    model = get_model(cell.arch.model_config(cfg))
    params = system.make_weights(model, seed)
    kprobe = system.KernelProbe(random.Random(seed))
    journal_dir = tempfile.mkdtemp(prefix="bench-sweep-")
    fsyncs = system.FsyncProbe(journal_dir)
    try:
        engine, encoder, memory, store = system.build(
            cfg, params, model, journal_dir, random.Random(seed), fsyncs)
        base = traffic.plan(mix, seed, seconds, cfg["vocab_size"])
        loop.write_all(engine, base.preload, cfg["serve"]["max_ingest_batch"])
        loop.warm_up(engine, encoder, store.forest, base, mix, cfg)
        for k, scale in enumerate(scales):
            m = copy.deepcopy(mix)
            for s in m["streams"].values():
                s["rate_per_s"] *= scale
            plan = traffic.plan(m, seed + 1 + k, seconds, cfg["vocab_size"])
            plan.questions, plan.preload = base.questions, []
            t0 = time.perf_counter()
            win = loop.run_window(engine, memory, plan, m, seconds)
            view = RunView(cfg, win, setup_s=0.0, encoder_calls=[], arch=cell.arch)
            out = {"scale": scale,
                   "rates": {n: s["rate_per_s"] for n, s in m["streams"].items()},
                   "wall_s": time.perf_counter() - t0, "close_s": win.close,
                   "attempted": len(win.requests),
                   "failed": sum(r.done is None for r in win.requests),
                   "metrics": {x["name"]: run.spec.reader(x["name"]).read(view)
                               for x in cell.end_to_end if x["name"] != "setup_s"},
                   "steps": len(win.step_ends)}
            for kind in ("query", "write", "lm"):
                out[kind] = _grows(view, kind, seconds)
            yield out
        store.close()
    finally:
        kprobe.remove()
        fsyncs.remove()
        shutil.rmtree(journal_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--scales", required=True)
    args = ap.parse_args(argv)
    cell = run.spec.resolve(args.workload)
    why = run.check_devices(cell.chips)
    if why:
        print(f"sweep: {why}", file=sys.stderr)
        return 1
    for out in sweep(cell, args.seed, args.seconds,
                     [float(s) for s in args.scales.split(",")]):
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
