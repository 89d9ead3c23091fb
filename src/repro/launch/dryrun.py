import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + \
    os.environ.get("REPRO_DRYRUN_DEVICES", "512")
os.environ.setdefault("JAX_PLATFORMS", "cpu")   # host devices, never a chip
# NOTE: the lines above MUST run before any jax import (device count is
# locked at first backend init).  Everything below is ordinary.

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402
from pathlib import Path  # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import ASSIGNED, SHAPES, get_config                 # noqa: E402
from repro.configs.base import ModelConfig, ShapeConfig                # noqa: E402
from repro.distributed.sharding import use_mesh                        # noqa: E402
from repro.launch.mesh import make_production_mesh, mesh_description   # noqa: E402
from repro.launch.roofline import (Roofline, collective_stats,         # noqa: E402
                                   model_flops_for)
from repro.launch import shardings as sh                               # noqa: E402
from repro.models import api, transformer as tf                       # noqa: E402
from repro.models.param import abstract_params                         # noqa: E402
from repro.training.optimizer import abstract_opt_state                # noqa: E402
from repro.training.step import auto_microbatches, make_train_step     # noqa: E402

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str | None:
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        return ("long_500k needs sub-quadratic attention; skipped for pure "
                "full-attention archs (DESIGN.md §4)")
    return None


def cell_name(arch: str, shape: str, multi_pod: bool) -> str:
    return f"{arch}__{shape}__{'pod2x16x16' if multi_pod else 'pod16x16'}"


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             collect_hlo: bool = True, cfg_overrides: dict | None = None,
             n_micro_override: int | None = None) -> dict:
    cfg = get_config(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = SHAPES[shape_name]
    rec: dict = {"arch": arch, "shape": shape_name,
                 "mesh": "2x16x16" if multi_pod else "16x16",
                 "kind": shape.kind, "ok": False}
    reason = skip_reason(cfg, shape)
    if reason:
        rec.update(skipped=True, reason=reason, ok=True)
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rec["mesh_info"] = mesh_description(mesh)
    n_dev = mesh.size
    t0 = time.time()
    with use_mesh(mesh):
        defs = tf.model_defs(cfg)
        params_s = abstract_params(defs)
        params_sh = sh.params_shardings(defs, mesh, shape.kind)
        batch_s = api.batch_struct(cfg, shape)
        batch_sh = sh.batch_shardings(batch_s, mesh)

        if shape.kind == "train":
            n_batch_shards = 1
            for a in ("pod", "data"):
                n_batch_shards *= mesh.shape.get(a, 1)
            n_micro = auto_microbatches(cfg, shape, n_batch_shards,
                                        seq_shard=mesh.shape.get("model", 1))
            if n_micro_override is not None:
                n_micro = n_micro_override
            rec["n_micro"] = n_micro
            step = make_train_step(cfg, n_micro=n_micro)
            opt_s = abstract_opt_state(params_s)
            opt_sh = sh.opt_shardings(params_sh)
            jitted = jax.jit(step,
                             in_shardings=(params_sh, opt_sh, batch_sh),
                             out_shardings=(params_sh, opt_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(params_s, opt_s, batch_s)
        else:
            cache_s = api.cache_struct(cfg, shape)
            cache_sh = sh.cache_shardings(cache_s, cfg, mesh)
            if shape.kind == "prefill":
                fn = api.make_prefill_fn(cfg)
            else:
                fn = api.make_decode_fn(cfg)
            jitted = jax.jit(fn,
                             in_shardings=(params_sh, batch_sh, cache_sh),
                             out_shardings=(None, cache_sh),
                             donate_argnums=(2,))
            lowered = jitted.lower(params_s, batch_s, cache_s)

        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

        ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):     # jax<0.5 returns [dict]
            ca = ca[0] if ca else {}
        flops_dev = float(ca.get("flops", 0.0))
        bytes_dev = float(ca.get("bytes accessed", 0.0))
        try:
            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
        except Exception as e:  # pragma: no cover
            rec["memory"] = {"error": str(e)}

        # loop-aware HLO parse: XLA's cost_analysis counts while bodies once,
        # undercounting scanned layer stacks by ~num_layers (see hlo_cost.py)
        from repro.launch.hlo_cost import analyze_hlo
        txt = compiled.as_text()
        hc = analyze_hlo(txt)
        rec["hlo_chars"] = len(txt)
        rec["collectives"] = {"counts": hc["coll_counts"],
                              "bytes_moved": hc["coll_bytes_per_dev"]}
        rec["xla_cost_analysis"] = {"flops_per_dev_unscaled": flops_dev,
                                    "bytes_per_dev_unscaled": bytes_dev}

        roof = Roofline(flops_per_dev=hc["dot_flops_per_dev"],
                        hbm_bytes_per_dev=hc["hbm_bytes_per_dev"],
                        coll_bytes_per_dev=hc["coll_bytes_per_dev"],
                        n_devices=n_dev,
                        model_flops=model_flops_for(cfg, shape))
        rec["roofline"] = roof.to_dict()
        rec["params"] = cfg.param_count()
        rec["active_params"] = cfg.active_param_count()
        rec["ok"] = True
    return rec


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(RESULTS_DIR))
    args = ap.parse_args()

    out_dir = Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    cells = []
    archs = ASSIGNED if args.all or args.arch is None else [args.arch]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    meshes = [False, True] if (args.both_meshes or args.all) else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    n_ok = n_fail = 0
    for a, s, mp in cells:
        name = cell_name(a, s, mp)
        path = out_dir / (name + ".json")
        if path.exists() and not args.force:
            print(f"[skip-cached] {name}")
            continue
        print(f"[run] {name} ...", flush=True)
        t0 = time.time()
        try:
            rec = run_cell(a, s, mp)
        except Exception as e:
            rec = {"arch": a, "shape": s,
                   "mesh": "2x16x16" if mp else "16x16", "ok": False,
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()[-4000:]}
        rec["wall_s"] = round(time.time() - t0, 2)
        path.write_text(json.dumps(rec, indent=2, default=str))
        status = "OK" if rec.get("ok") else "FAIL"
        if rec.get("skipped"):
            status = "SKIP"
        print(f"[{status}] {name} ({rec['wall_s']}s)"
              + ("" if rec.get("ok") else f" :: {rec.get('error')}"), flush=True)
        n_ok += int(bool(rec.get("ok")))
        n_fail += int(not rec.get("ok"))
    print(f"done: {n_ok} ok, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
