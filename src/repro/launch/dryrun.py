"""Multi-pod dry run: lower + compile EVERY (architecture x input shape) on
the production meshes, prove the sharding is coherent, and extract the
roofline inputs (memory analysis, cost analysis, collective bytes).

    PYTHONPATH=src python -m repro.launch.dryrun --mesh both --out artifacts/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3_8b --shape decode_32k

Per cell this produces artifacts/dryrun/<arch>__<shape>__<mesh>.json with:
compile status+time, per-device memory analysis, raw cost_analysis numbers,
collective bytes by kind (while-trip-count expanded), and the three roofline
terms. EXPERIMENTS.md §Dry-run / §Roofline read these artifacts.
"""
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, ShapeConfig, TrainConfig
from repro.configs import ARCHS, get_config
from repro.configs.shapes import SHAPE_ORDER, SHAPES, shape_applicable
from repro.launch import sharding as shd
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import roofline_terms
from repro.models import get_model
from repro.models.factory import input_specs
from repro.training.train_loop import make_train_step, train_state_specs


# ---------------------------------------------------------------------------
# cache shardings (name-based, like param rules)
# ---------------------------------------------------------------------------
def cache_shardings(cfg: ModelConfig, mesh, specs=None) -> Any:
    model = get_model(cfg)
    if specs is None:
        specs = model.cache_specs(2, 8)  # structure probe (tests only)
    names = tuple(mesh.axis_names)
    model_ok = lambda n: "model" if ("model" in names and n % dict(mesh.shape)["model"] == 0) else None

    def rule(path: str, leaf) -> P:
        name = path.split("/")[-1]
        nd = leaf.ndim
        DATA = tuple(a for a in ("pod", "data") if a in names)
        if name in ("k", "v", "kv_k", "kv_v", "self_k", "self_v", "cross_k", "cross_v"):
            # (L, B, S, Hkv, Dh): heads over model if divisible, else the
            # sequence dim (split-KV decode) so the cache never replicates
            h_ax = model_ok(cfg.num_kv_heads)
            s_ax = "model" if (h_ax is None and "model" in names) else None
            return P(None, DATA, s_ax, h_ax, None)
        if name == "wkv":      # (L, B, H, K, V)
            return P(None, DATA, model_ok(cfg.d_model // max(cfg.rwkv_head_size, 1)), None, None)
        if name == "ssd":      # (L, B, H, P, N)
            h = cfg.d_inner // max(cfg.ssm_head_dim, 1)
            return P(None, DATA, model_ok(h), None, None)
        if name == "conv":     # (L, B, W-1, C)
            return P(None, DATA, None, None)
        if name in ("shift_t", "shift_c"):  # (L, B, D)
            return P(None, DATA, None)
        if name == "lengths":  # (B,)
            return P(DATA)
        return P(*([None] * nd))

    def one(keypath, leaf):
        spec = rule(shd._path_str(keypath), leaf)
        spec = shd._drop_indivisible(spec, leaf, mesh)
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(one, specs)


def _resize_cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    model = get_model(cfg)
    return model.cache_specs(batch, max_len)


# ---------------------------------------------------------------------------
def build_cell(cfg: ModelConfig, shape: ShapeConfig, mesh, tcfg: TrainConfig,
               *, kv_replicate: bool = False):
    """Returns (fn, args_specs, in_shardings)."""
    model = get_model(cfg)
    kvh = cfg.num_kv_heads if kv_replicate else 0
    batch_sds = input_specs(cfg, shape)
    batch_sh = {k: shd.data_sharding(mesh, v.ndim, batch_size=v.shape[0])
                for k, v in batch_sds.items()}

    if shape.is_train:
        state_sds = train_state_specs(model, tcfg)
        state_sh: Dict[str, Any] = {
            "params": shd.param_shardings(mesh, state_sds["params"],
                                          moe_fsdp=cfg.moe_fsdp_params,
                                          kv_heads=kvh),
            "opt": {
                "m": shd.zero1_shardings(mesh, state_sds["opt"]["m"]),
                "v": shd.zero1_shardings(mesh, state_sds["opt"]["v"]),
                "step": NamedSharding(mesh, P()),
            },
        }
        if "err" in state_sds:
            state_sh["err"] = shd.zero1_shardings(mesh, state_sds["err"])
        step = make_train_step(model, tcfg)
        return step, (state_sds, batch_sds), (state_sh, batch_sh)

    param_sds = jax.eval_shape(model.init, jax.random.key(0))
    param_sh = shd.param_shardings(mesh, param_sds, moe_fsdp=cfg.moe_fsdp_params,
                                   kv_heads=kvh)

    if shape.kind == "prefill":
        fn = lambda params, batch: model.prefill(params, batch, shape.seq_len)
        return fn, (param_sds, batch_sds), (param_sh, batch_sh)

    # decode: one token against a seq_len cache
    cache_sds = _resize_cache_specs(cfg, shape.global_batch, shape.seq_len)
    cache_sh = cache_shardings(cfg, mesh, cache_sds)
    fn = model.decode
    return fn, (param_sds, batch_sds, cache_sds), (param_sh, batch_sh, cache_sh)


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             tcfg: Optional[TrainConfig] = None,
             out_dir: Optional[str] = None,
             cfg_override: Optional[ModelConfig] = None,
             shape_override: Optional[ShapeConfig] = None,
             mesh_override=None, tag: str = "",
             kv_replicate: bool = False,
             donate: bool = False) -> Dict[str, Any]:
    cfg = cfg_override or get_config(arch)
    shape = shape_override or SHAPES[shape_name]
    tcfg = tcfg or TrainConfig(microbatch_size=0, grad_compression="none", zero1=True)
    mesh = mesh_override if mesh_override is not None else \
        make_production_mesh(multi_pod=(mesh_kind == "multi"))
    num_devices = mesh.size
    tp = dict(mesh.shape)["model"]

    result: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind + tag,
        "mesh_shape": list(mesh.shape.values()) if isinstance(mesh.shape, dict) else list(mesh.shape),
        "ok": False,
    }
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        result["skipped"] = reason
        result["ok"] = True
        _dump(result, out_dir)
        return result

    try:
        fn, args, in_sh = build_cell(cfg, shape, mesh, tcfg,
                                     kv_replicate=kv_replicate)
        donate_args = ()
        if donate:
            # deployment aliasing: train state / decode cache update in place
            donate_args = (0,) if shape.is_train else (
                (2,) if shape.kind == "decode" else ())
        t0 = time.time()
        with jax.set_mesh(mesh):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate_args).lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0

        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        ca = ca[0] if isinstance(ca, list) else (ca or {})
        txt = compiled.as_text()
        colls = collective_bytes(txt)

        terms = roofline_terms(
            cfg, shape, num_devices=num_devices, tp=tp,
            collective_bytes_per_dev=colls.get("total", 0.0),
        )
        result.update(
            ok=True,
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            memory={
                "argument_gb": ma.argument_size_in_bytes / 1e9,
                "output_gb": ma.output_size_in_bytes / 1e9,
                "temp_gb": ma.temp_size_in_bytes / 1e9,
                "peak_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes) / 1e9,
            },
            cost_analysis_raw={
                "flops": ca.get("flops", -1.0),
                "bytes_accessed": ca.get("bytes accessed", -1.0),
            },
            collectives={k: v for k, v in colls.items()},
            roofline=terms,
        )
    except Exception as e:  # noqa: BLE001 — report compile failures as data
        result["error"] = f"{type(e).__name__}: {e}"
        result["traceback"] = traceback.format_exc()[-2000:]
    _dump(result, out_dir)
    return result


def _dump(result: Dict[str, Any], out_dir: Optional[str]) -> None:
    if not out_dir:
        return
    os.makedirs(out_dir, exist_ok=True)
    name = f"{result['arch']}__{result['shape']}__{result['mesh']}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(result, f, indent=1, default=float)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced configs on a tiny (2,4)/(2,2,2) mesh — CI")
    # --- perf-iteration knobs (EXPERIMENTS.md §Perf) -----------------------
    ap.add_argument("--tag", default="", help="suffix for artifact filenames")
    ap.add_argument("--mesh-shape", default=None,
                    help="override single-pod mesh, e.g. 64x4 (256 chips)")
    ap.add_argument("--serving-ep", action="store_true",
                    help="pure expert-parallel MoE weights (no FSDP)")
    ap.add_argument("--kv-replicate", action="store_true",
                    help="replicate wk/wv when kv_heads %% tp != 0")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--donate", action="store_true",
                    help="donate train state / decode cache buffers")
    args = ap.parse_args()

    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = SHAPE_ORDER if args.shape == "all" else [args.shape]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    tcfg = TrainConfig(microbatch_size=args.microbatch)

    n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for mesh_kind in meshes:
                if args.smoke:
                    from repro.configs import get_smoke_config
                    from repro.launch.mesh import make_mesh
                    cfg_o = get_smoke_config(arch)
                    shape_o = dataclasses.replace(
                        SHAPES[shape_name],
                        seq_len=64 if SHAPES[shape_name].kind != "decode" else 128,
                        global_batch=4,
                    )
                    mesh_o = make_mesh((2, 2, 2), ("pod", "data", "model")) \
                        if mesh_kind == "multi" else make_mesh((2, 4), ("data", "model"))
                    r = run_cell(arch, shape_name, mesh_kind, tcfg=tcfg,
                                 out_dir=args.out, cfg_override=cfg_o,
                                 shape_override=shape_o, mesh_override=mesh_o)
                else:
                    cfg_o = get_config(arch)
                    if args.serving_ep:
                        cfg_o = cfg_o.replace(moe_fsdp_params=False)
                    if args.no_remat:
                        cfg_o = cfg_o.replace(remat=False)
                    mesh_o = None
                    if args.mesh_shape and mesh_kind == "single":
                        from repro.launch.mesh import make_mesh
                        dims = tuple(int(x) for x in args.mesh_shape.split("x"))
                        mesh_o = make_mesh(dims, ("data", "model"))
                    r = run_cell(arch, shape_name, mesh_kind, tcfg=tcfg,
                                 out_dir=args.out, cfg_override=cfg_o,
                                 mesh_override=mesh_o, tag=args.tag,
                                 kv_replicate=args.kv_replicate,
                                 donate=args.donate)
                if r.get("skipped"):
                    status = "SKIP " + r["skipped"][:40]
                elif r["ok"]:
                    t = r["roofline"]
                    status = (
                        f"ok compile={r['compile_s']:.0f}s peak={r['memory']['peak_gb']:.1f}GB "
                        f"comp={t['compute_s']*1e3:.2f}ms mem={t['memory_s']*1e3:.2f}ms "
                        f"coll={t['collective_s']*1e3:.2f}ms dom={t['dominant']}"
                    )
                else:
                    status = "FAIL " + r.get("error", "?")[:80]
                    n_fail += 1
                print(f"[{arch:16s}|{shape_name:12s}|{mesh_kind:6s}] {status}", flush=True)
    if n_fail:
        raise SystemExit(f"{n_fail} cells failed")


if __name__ == "__main__":
    main()
