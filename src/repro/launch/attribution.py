"""Per-op collective attribution for compiled HLO — the §Perf profiler.

    PYTHONPATH=src python -m repro.launch.attribution --arch llama3_8b \
        --shape train_4k [--mesh-shape 64x4] [--microbatch 64] [--kv-replicate]

Prints the top collective ops by EFFECTIVE bytes (while-loop trip counts
expanded, nested loops multiplied), with shapes and jax op_name metadata —
how the B5/C3 §Perf fixes were found.
"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import re  # noqa: E402
from typing import Dict, List, Tuple  # noqa: E402

import jax  # noqa: E402

from repro.config import TrainConfig  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.configs.shapes import SHAPES  # noqa: E402
from repro.launch.hlo_analysis import (  # noqa: E402
    _CONST_RE, _SHAPE_RE, _WHILE_RE, _shape_bytes, parse_computations,
)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def attribute(hlo_text: str, top: int = 15) -> List[Tuple[float, str, int, str, List[str]]]:
    comps = parse_computations(hlo_text)

    parents: Dict[str, Tuple[str, str]] = {}  # body -> (parent, cond)
    for parent, lines in comps.items():
        for line in lines:
            w = _WHILE_RE.search(line)
            if w:
                parents[w.group(2)] = (parent, w.group(1))

    def trip(cond: str) -> int:
        best = 1
        for l2 in comps.get(cond, []):
            for c in _CONST_RE.finditer(l2):
                best = max(best, int(c.group(1)))
        return best

    def eff_mult(cname: str, seen=()) -> int:
        if cname not in parents or cname in seen:
            return 1
        parent, cond = parents[cname]
        return trip(cond) * eff_mult(parent, seen + (cname,))

    rows = []
    for cname, lines in comps.items():
        mult = eff_mult(cname)
        for line in lines:
            s = line.strip()
            for op in _COLLECTIVES:
                if re.search(rf"\s{op}(-start)?\(", s):
                    lhs = s.split(f"{op}(")[0].split(f"{op}-start(")[0]
                    b = _shape_bytes(lhs.split("=", 1)[-1])
                    mm = re.search(r'op_name="([^"]+)"', s)
                    name = mm.group(1)[-80:] if mm else "?"
                    shapes = [m.group(0) for m in _SHAPE_RE.finditer(
                        lhs.split("=", 1)[-1])][:4]
                    rows.append((b * mult, op, mult, name, shapes))
                    break
    rows.sort(reverse=True)
    return rows[:top]


def main() -> None:
    from repro.launch.dryrun import build_cell
    from repro.launch.mesh import make_mesh, make_production_mesh

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh-shape", default=None)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--kv-replicate", action="store_true")
    ap.add_argument("--serving-ep", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args()

    cfg = get_config(args.arch)
    if args.serving_ep:
        cfg = cfg.replace(moe_fsdp_params=False)
    if args.mesh_shape:
        dims = tuple(int(x) for x in args.mesh_shape.split("x"))
        mesh = make_mesh(dims, ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    tcfg = TrainConfig(microbatch_size=args.microbatch)

    fn, cell_args, in_sh = build_cell(cfg, SHAPES[args.shape], mesh, tcfg,
                                      kv_replicate=args.kv_replicate)
    with jax.set_mesh(mesh):
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*cell_args).compile()
    for b, op, mult, name, shapes in attribute(compiled.as_text(), args.top):
        print(f"{b/1e9:8.1f}GB  {op:18s} x{mult:<5d} {shapes}  {name}")


if __name__ == "__main__":
    main()
