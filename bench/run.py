#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json once, on the chips it names.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up builds the configuration's model, as its architecture's module
(``bench/arch/<arch>.py``, named by the file's ``"arch"``) describes it,
with weights made from the seed, its memory (journal fsynced on every
write) and one ServeEngine; writes the history the mix preloads; warms up
every shape the window can use. The window then drives the engine with
the mix's traffic for ``--seconds``. With ``--trace 0`` the result carries
the cell's end-to-end metrics; with ``--trace 1`` the program's spans and
the profiler are on, and it carries the per-layer metrics, the device's
busy time and a breakdown.

After the window, with the device's peak memory read and the engine freed,
the outputs of the timed path are compared with plain references
(``checks.py``). Each number compared is printed beside its limit on the
last lines of standard error and under ``checks`` in the result.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``).
Without a TPU, or with fewer chips than the cell names, it exits non-zero
and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import random  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(ROOT, "src"))

import checks  # noqa: E402
import spec  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout;
    every program is kept, however fast it compiled."""
    import jax

    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _spans(sink, t0: float):
    from view import Span

    return [Span(r["name"], r["ts"] - t0, r["dur_s"], r.get("attrs") or {})
            for r in sink.spans()]


def run_cell(cell, seed: int, seconds: float, trace: bool, *,
             t_start: float = T_START, control: bool = False,
             plant=None, cache: bool = True) -> dict:
    """One run of ``cell``. Returns the result object, with ``checks`` (and,
    with ``control``, ``control_checks``) beside the contract's keys.
    ``plant(engine, encoder, memory, kprobe)``, where given, is called after
    warm-up: the tests break the timed path with it."""
    import jax

    import loop
    import system
    import traffic
    from compile_log import CompileLog
    from peaks import peaks as peak_table
    from repro.models import get_model
    from view import RunView

    if cache:
        enable_compile_cache()
    compiles = CompileLog()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu"
    cfg, mix, arch = cell.config, cell.traffic, cell.arch
    rng = random.Random(seed)
    model = get_model(arch.model_config(cfg))
    params = system.make_weights(model, seed)
    journal_dir = tempfile.mkdtemp(prefix="bench-journal-")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    kprobe = system.KernelProbe(random.Random(rng.random()))
    fsyncs = system.FsyncProbe(journal_dir)
    try:
        engine, encoder, memory, store = system.build(
            cfg, params, model, journal_dir, random.Random(rng.random()), fsyncs)
        plan = traffic.plan(mix, seed, seconds, cfg["vocab_size"])
        loop.write_all(engine, plan.preload, cfg["serve"]["max_ingest_batch"])
        loop.warm_up(engine, encoder, store.forest, plan, mix, cfg)
        if plant is not None:
            plant(engine, encoder, memory, kprobe)

        sink = None
        if trace:
            from repro import obs

            sink = obs.MemorySink()
            popts = jax.profiler.ProfileOptions()
            popts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=popts)
            tr_t0 = obs.enable_tracing(sink).t0
        compiles_before = compiles.count
        setup_s = time.perf_counter() - t_start
        encoder.active = kprobe.active = True
        with jax.profiler.TraceAnnotation("bench.window"):
            win = loop.run_window(engine, memory, plan, mix, seconds)
        encoder.active = kprobe.active = False
        in_window = compiles.count - compiles_before
        spans = None
        if trace:
            obs.disable_tracing()
            jax.profiler.stop_trace()
            spans = _spans(sink, win.t0 - tr_t0)
        stats = dev.memory_stats() or {}
        peak_bytes = stats.get("peak_bytes_in_use")

        # what the acknowledged writes left in the memory, read before the
        # program's state is freed for the references
        acked = [r.payload for r in win.requests
                 if r.kind == "write" and r.done is not None]
        acked += win.sessions
        t_check = time.perf_counter()
        written = {
            "applied_missing": checks.applied_missing(store.forest, acked, plan.planted),
            "unflushed": checks.unflushed(store.forest, acked),
            "unsynced_acks": checks.unsynced_acks(acked, memory.unsynced),
        }
        rows = checks.index_rows(store.forest, acked, random.Random(rng.random()),
                                 mix["check"]["index_rows"])
        store.close()
        engine.cache = None
        del engine, memory, store
        gc.collect()
    finally:
        kprobe.remove()
        fsyncs.remove()
    t_recover = time.perf_counter()
    written["recovered_missing"] = checks.applied_missing(
        system.recover(journal_dir, cfg["hidden_size"]), acked, plan.planted)
    recover_s = time.perf_counter() - t_recover

    reduced = None
    if trace:
        import glob

        import trace_reduce

        path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                                recursive=True))[-1]
        reduced = trace_reduce.load(path)
        shutil.rmtree(trace_dir, ignore_errors=True)

    pk = peak_table(dev.device_kind) if on_tpu else None
    view = RunView(cfg, win, setup_s=setup_s, encoder_calls=encoder.calls,
                   spans=spans, trace=reduced, peaks=pk, arch=arch)
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = spec.reader(m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    numbers = _compare(arch, cfg, mix, params, win, encoder, kprobe, rows,
                       random.Random(rng.random()), control=False)
    numbers.update(written)
    check_s = time.perf_counter() - t_check
    result = {
        "correct": checks.judge(numbers, cfg["limits"], _required(mix)),
        "attempted": len(win.requests) + len(win.sessions),
        "failed": sum(1 for r in win.requests if r.done is None),
        "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak_bytes},
    }
    if trace:
        import trace_reduce

        result["device"]["busy_s"] = trace_reduce.busy_s(reduced)
        result["device"]["window_s"] = reduced.window_s
        w0 = reduced.window[0]          # the window's open on the trace clock
        tags = [(s.name, w0 + s.start * 1e9, w0 + (s.start + s.dur) * 1e9)
                for s in spans]
        result["breakdown"] = {
            "device_ops": trace_reduce.top_ops(reduced),
            "idle_gaps": trace_reduce.idle_gaps(reduced, tags),
        }
    if control:
        result["control_checks"] = _compare(
            arch, cfg, mix, params, win, encoder, kprobe, rows,
            random.Random(rng.random()), control=True)
    shutil.rmtree(journal_dir, ignore_errors=True)
    late = sorted(r.submitted - r.due for r in win.requests if r.submitted is not None)
    result["notes"] = {
        "compiles_in_window": in_window,
        "compiles_total": compiles.count,
        "persistent_cache_hits": compiles.cache_hits,
        "generator_late_ms_p50": 1000 * late[len(late) // 2] if late else 0.0,
        "generator_late_ms_max": 1000 * late[-1] if late else 0.0,
        "window_s": win.close, "steps": len(win.step_ends),
        "sessions_acked": len(win.sessions),
        "recover_s": recover_s, "check_s": check_s,
        "failed_by_kind": {k: sum(1 for r in win.requests if r.kind == k and r.done is None)
                           for k in ("query", "write", "lm")},
    }
    result["checks"] = {k: {"value": v["value"], "limit": cfg["limits"].get(k),
                            "compared": v["compared"]}
                        for k, v in numbers.items() if v is not None}
    return result


def _required(mix: dict) -> list:
    need = ["encoder_gap", "applied_missing", "recovered_missing", "unflushed",
            "unsynced_acks"]
    streams = mix.get("streams", {})
    if "lm" in streams:
        need.append("lm_gap")
    if "query" in streams:
        need += ["topk_gap", "browse_err"]
    return need


def _compare(arch, cfg, mix, params, win, encoder, kprobe, index_rows, rng,
             *, control: bool) -> dict:
    chk = mix["check"]
    s = kprobe.samples
    return {
        "lm_gap": checks.lm_gap(arch, params, cfg,
                                [r for r in win.requests if r.kind == "lm"],
                                rng, chk["lm_requests"], control=control),
        "encoder_gap": checks.encoder_gap(
            arch, params, cfg, encoder.sample.items, rng, chk["encoder_texts"],
            cfg["serve"]["encoder_max_tokens"], control=control,
            index_rows=index_rows),
        "topk_gap": checks.topk_gap(s["topk_sim"].items, control=control),
        "browse_err": checks.browse_err(s["browse_scores"].items, control=control),
        "refresh_err": checks.refresh_err(s["tree_refresh"].items, control=control),
    }


def check_devices(chips: int) -> str:
    """'' when JAX sees at least ``chips`` TPU chips, else why not."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        return f"no TPU: JAX found {devs[0].platform} devices only"
    if len(devs) < chips:
        return f"{chips} TPU chips needed, {len(devs)} found"
    return ""


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = spec.resolve(args.workload)
    except (KeyError, OSError) as e:
        log(f"bench: {e}")
        return 2
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        log("bench: the program (src/repro) is not in this checkout")
        return 2
    why = check_devices(cell.chips)
    if why:
        log(f"bench: {why}")
        return 1
    res = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    log("notes " + json.dumps(res.pop("notes")))
    chk = res.pop("checks")
    for name, c in chk.items():
        log(f"check {name} = {c['value']!r} (limit {c['limit']!r}, "
            f"{c['compared']} compared)")
    res["checks"] = {k: {"value": c["value"], "limit": c["limit"]}
                     for k, c in chk.items()}
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
