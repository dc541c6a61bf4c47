"""Multi-pod dry-run: lower + compile every (arch x input-shape x mesh)
combination on placeholder devices and record memory / cost /
collective analyses.

MUST set the host-device flag before any jax import (jax locks the
device count on first init); the shared helper appends to any
user-provided ``XLA_FLAGS`` instead of clobbering them.
"""
from repro.launch.hostdev import child_env, force_host_device_count

force_host_device_count(512)

# ruff: noqa: E402
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, dryrun_matrix, get_config
from repro.core import archcost
from repro.launch import hlo as hlo_mod
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_production_mesh
from repro.models import sharding as shd
from repro.optim.sgd import sgd

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _batch_shardings(cfg, shape, specs, sc, mesh):
    out = {}
    for name, s in specs.items():
        if name == "cache":
            out[name] = jax.tree_util.tree_map(
                lambda sp: NamedSharding(mesh, sp),
                shd.cache_specs(s, sc))
        elif name == "pos":
            out[name] = NamedSharding(mesh, P())
        else:
            nd = len(s.shape)
            spec = shd.resolve_spec(s.shape, [["batch"]] + [()] * (nd - 1), sc)
            out[name] = NamedSharding(mesh, spec)
    return out


def dryrun_one(arch: str, shape_name: str, *, multi_pod: bool,
               mode: str = "fsdp", remat: bool = True,
               save_hlo: str | None = None,
               donate: bool = True, accum_steps: int = 1) -> dict:
    t_start = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    sc = shd.ShardingConfig(mesh_axes=mesh.axis_names, mode=mode)
    shd.set_sharding(sc)
    shd.set_mesh_sizes(dict(zip(mesh.axis_names, mesh.devices.shape)))

    pshape = steps_mod.params_shape(cfg)
    pspecs = shd.named_shardings(pshape, sc, mesh)
    specs = steps_mod.input_specs(cfg, shape)
    in_batch_shardings = _batch_shardings(cfg, shape, specs, sc, mesh)

    record: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": mode, "remat": remat, "accum_steps": accum_steps,
        "n_devices": mesh.devices.size,
        "status": "ok",
    }

    try:
        if shape.kind == "train":
            opt = sgd(lr=1e-2, momentum=0.9)
            oshape = jax.eval_shape(opt.init, pshape)
            ospecs = shd.named_shardings(oshape, sc, mesh)
            step = steps_mod.make_train_step(cfg, opt, remat=remat,
                                             accum_steps=accum_steps)
            jitted = jax.jit(step,
                             in_shardings=(pspecs, ospecs, in_batch_shardings),
                             out_shardings=(pspecs, ospecs, None),
                             donate_argnums=(0, 1) if donate else ())
            args = (pshape, oshape, specs)
        elif shape.kind == "prefill":
            step = steps_mod.make_prefill_step(cfg)
            jitted = jax.jit(step, in_shardings=(pspecs, in_batch_shardings))
            args = (pshape, specs)
        else:
            step = steps_mod.make_serve_step(cfg)
            cache_shardings = in_batch_shardings["cache"]
            jitted = jax.jit(
                step,
                in_shardings=(pspecs, in_batch_shardings),
                out_shardings=(None, cache_shardings),
                donate_argnums=(1,) if donate else ())
            args = (pshape, specs)

        with jax.sharding.set_mesh(mesh):
            t0 = time.time()
            lowered = jitted.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()

        mem = compiled.memory_analysis()
        record["lower_s"] = round(t1 - t0, 2)
        record["compile_s"] = round(t2 - t1, 2)
        record["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        }
        ca = compiled.cost_analysis()
        if isinstance(ca, list):
            ca = ca[0]
        record["cost_analysis"] = {
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
            "while_body_counted_once": True,
        }
        txt = compiled.as_text()
        stats = hlo_mod.collective_stats(txt, loop_trip_count=max(cfg.num_units, 1))
        record["collectives"] = stats.to_dict()
        record["hlo_bytes"] = len(txt)
        if save_hlo:
            Path(save_hlo).write_text(txt)

        cost = archcost.step_cost(cfg, shape)
        record["analytic"] = {
            "flops": cost.flops, "hbm_bytes": cost.hbm_bytes,
            "model_flops": cost.model_flops,
            "n_params": cost.n_params,
            "n_active_params": cost.n_active_params,
            "param_bytes": cost.param_bytes,
        }
    except Exception as e:  # noqa: BLE001 — record the failure, don't crash the sweep
        record["status"] = "error"
        record["error"] = f"{type(e).__name__}: {e}"
        record["traceback"] = traceback.format_exc()
    record["total_s"] = round(time.time() - t_start, 2)
    return record


def result_path(arch: str, shape: str, multi_pod: bool, out_dir: Path) -> Path:
    mesh = "2x16x16" if multi_pod else "16x16"
    return out_dir / f"{arch}__{shape}__{mesh}.json"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="run the full matrix in subprocesses")
    ap.add_argument("--missing-only", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--accum-steps", type=int, default=1)
    ap.add_argument("--mode", default="fsdp",
                    choices=("fsdp", "fsdp2d", "zero3", "pure_dp"))
    ap.add_argument("--save-hlo")
    ap.add_argument("--out-dir", default=str(RESULTS_DIR))
    args = ap.parse_args(argv)
    out_dir = Path(args.out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)

    if args.all:
        combos = [(a, s, mp) for (a, s) in dryrun_matrix()
                  for mp in (False, True)]
        failures = 0
        for i, (a, s, mp) in enumerate(combos):
            path = result_path(a, s, mp, out_dir)
            if args.missing_only and path.exists():
                ok = json.loads(path.read_text()).get("status") == "ok"
                if ok:
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", a, "--shape", s, "--out-dir", str(out_dir),
                   "--mode", args.mode]
            if mp:
                cmd.append("--multi-pod")
            print(f"[{i + 1}/{len(combos)}] {a} x {s} x "
                  f"{'2x16x16' if mp else '16x16'}", flush=True)
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=3600, env=child_env(512))
            if r.returncode != 0:
                failures += 1
                print(r.stdout[-2000:], r.stderr[-2000:], flush=True)
        print(f"done; {failures} subprocess failures")
        return 1 if failures else 0

    if not (args.arch and args.shape):
        ap.error("--arch and --shape required (or --all)")
    rec = dryrun_one(args.arch, args.shape, multi_pod=args.multi_pod,
                     mode=args.mode, remat=not args.no_remat,
                     save_hlo=args.save_hlo, accum_steps=args.accum_steps)
    path = result_path(args.arch, args.shape, args.multi_pod, out_dir)
    path.write_text(json.dumps(rec, indent=2))
    print(json.dumps({k: v for k, v in rec.items() if k != "traceback"},
                     indent=2))
    return 0 if rec["status"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
