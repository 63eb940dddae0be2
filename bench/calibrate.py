#!/usr/bin/env python3
"""Readings for the limits of ``correct``: one process runs a cell on
several seeds and, for each, reads every number the run compares twice:
for the program, and for the control (the reference in the next lower
precision put in the program's place). One JSON line per seed.

    python3 bench/calibrate.py --workload <cell> --seconds <s> --seeds 1,2,3

The limits in the configuration's file are set between the largest reading
of the program and the smallest of the control (see PERF.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    cell = run.spec.resolve(args.workload)
    why = run.check_devices(cell.chips)
    if why:
        print(f"calibrate: {why}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        res = run.run_cell(cell, seed, args.seconds, False,
                           t_start=time.perf_counter(), control=True)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "program": res["checks"],
                          "control": res["control_checks"],
                          "metrics": res["metrics"], "notes": res["notes"],
                          "device": res["device"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
