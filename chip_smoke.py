"""Bring-up check of the what-if engine on TPU chips.

    python chip_smoke.py              # one chip: phases A, B and C
    python chip_smoke.py --chips 4    # four chips: the multi-chip paths

One process drives every phase and starts no child, so it alone holds
the chip.  It refuses to run without a TPU (it never falls back to the
CPU), prints informational lines first — compile seconds as set-up,
warm seconds, rates, agreement — and, only when every check passed,
ends with one JSON line::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Phases on one chip:

* **A. sweep kernel** — ``sweep(grid, backend="jax")`` on the frontier
  (51 840 scenarios), het/straggler and failure grids: the kernel's
  outputs live on the TPU, and every numeric column agrees with
  ``backend="numpy"`` to <= 1e-6 relative;
* **B. served path** — an in-process :class:`SweepService` answers two
  singleton queries and a pair coalesced into one kernel call, each
  equal to a direct ``sweep()`` of its grid (bit-identity reported);
* **C. measured train step** — :func:`repro.measure.run.run_measurement`
  of qwen1.5-4b at its published width in bf16, depth cut to 4 layers
  (segmentation depths 2 and 4), seq 1024, batch 2, one chip: finite
  losses under every sync policy, attention through the Pallas kernel
  (``tpu_custom_call`` in the step's HLO), and a fresh ``jax:`` trace
  that sweeps.

With ``--chips 4`` only the two paths that span chips run, each against
its one-chip reference: the frontier kernel on the automatic 4-device
mesh against an explicit one-device mesh (columns equal), and the
data-parallel step at phase C's width on 4 chips with one sequence per
chip, against one chip running the same 4 sequences (loss and gradients
within bf16 tolerance, HLO collective bytes equal to the payload).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT_DIR = ROOT / "results" / "chip_smoke"

#: qwen1.5-4b as measured here: published width and dtype, 4 of its 40
#: layers (the only cut), 1k-token sequences.
ARCH = "qwen1.5-4b"
SEQ_LEN = 1024
NUM_LAYERS = 4

#: Agreement gates: the jax/NumPy contract, and the bf16 tolerance for
#: a 4-chip step against one chip running the same sequences.
AGREEMENT_REL = 1e-6
BF16_REL = 2e-2


def info(msg: str) -> None:
    print(msg, flush=True)


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise AssertionError(msg)


# ----------------------------------------------------------------------
# Column comparison
# ----------------------------------------------------------------------
def max_rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, dtype=np.float64)
    want = np.asarray(want, dtype=np.float64)
    err = np.abs(got - want)
    scale = np.abs(want)
    rel = np.where(scale > 0, err / np.where(scale > 0, scale, 1.0),
                   np.where(err > 0, np.inf, 0.0))
    return float(rel.max()) if rel.size else 0.0


def compare_tables(got: dict, want: dict) -> tuple[dict, bool]:
    """``({numeric column: max rel err}, bit_identical)``; label
    columns must be equal outright."""
    import numpy as np

    errs, identical = {}, True
    for k, w in want.items():
        g = got[k]
        if np.asarray(w).dtype.kind in "fiu":
            errs[k] = max_rel_err(g, w)
            identical &= bool(np.array_equal(g, w))
        else:
            check(np.array_equal(g, w), f"label column {k} differs")
    return errs, identical


# ----------------------------------------------------------------------
# Phase A: the sweep kernel
# ----------------------------------------------------------------------
def phase_a() -> None:
    import jax
    import numpy as np

    from benchmarks.bench_sweep_throughput import (failure_grid,
                                                   het_straggler_grid)
    from repro.core.batched_jax import jax_grid_evaluator
    from repro.core.scenarios import frontier_grid
    from repro.core.sweep import sweep

    for name, grid in (("frontier", frontier_grid()),
                       ("het_straggler", het_straggler_grid()),
                       ("failure", failure_grid())):
        t0 = time.perf_counter()
        jev = jax_grid_evaluator(grid)
        with jax.enable_x64(True):
            out = jev._traced_columns()
            jax.block_until_ready(out)
        compile_s = time.perf_counter() - t0
        platforms = {d.platform for v in out.values() for d in v.devices()}
        check(platforms == {"tpu"},
              f"A/{name}: kernel outputs live on {platforms}, not the TPU")
        check(all(v.dtype == np.float64 for v in out.values()),
              f"A/{name}: kernel outputs are not float64")
        sweep(grid, backend="jax")                       # warm host caches
        t0 = time.perf_counter()
        rj = sweep(grid, backend="jax")
        warm_s = time.perf_counter() - t0
        rn = sweep(grid, backend="numpy")
        errs, identical = compare_tables(rj.columns, rn.columns)
        worst = max(errs.values())
        info(f"A/{name}: {len(grid)} scenarios; set-up (compile) "
             f"{compile_s:.2f}s; warm sweep {warm_s:.4f}s = "
             f"{len(grid) / warm_s:,.0f} scenarios/s; outputs on "
             f"{sorted(platforms)}; max rel err vs numpy per column "
             f"{json.dumps(errs)}; bit-identical to numpy: {identical}")
        check(worst <= AGREEMENT_REL,
              f"A/{name}: jax vs numpy max rel err {worst:.3e} > "
              f"{AGREEMENT_REL}")


# ----------------------------------------------------------------------
# Phase B: the served path
# ----------------------------------------------------------------------
#: The pair coalesced into one kernel call: a frontier slice and a
#: het/straggler slice of the same workload (same padded depth), seed 7.
PAIR = [
    {"grid": "frontier", "workloads": ["resnet50"], "workers": [8],
     "backend": "jax", "seed": 7},
    {"workloads": ["resnet50"], "workers": [4, 16],
     "policies": ["tensorflow", "bucketed-4mb", "priority"],
     "het": ["het:1x0.5+3x1.0", "het:2x1.0@bw0.5"],
     "stragglers": ["none", "lognormal:0.2x100"],
     "backend": "jax", "seed": 7},
]
SINGLETONS = [
    {"workloads": ["googlenet"], "workers": [4, 8], "backend": "jax",
     "seed": 3},
    {"grid": "mixed", "workloads": ["alexnet"], "workers": [16],
     "sync_k": ["none", "12"], "faults": ["none", "fail:0.01@restart2.5x100"],
     "backend": "jax", "seed": 3},
]


def phase_b() -> None:
    from repro.core.scenarios import grid_from_spec
    from repro.core.service import SweepService
    from repro.core.sweep import sweep

    def direct(spec):
        grid = grid_from_spec({k: v for k, v in spec.items()
                               if k not in ("backend", "seed")})
        return sweep(grid, backend="jax", seed=spec["seed"]).columns

    with SweepService(window_s=0.5, max_coalesce=8) as svc:
        answers = []
        for spec in SINGLETONS:
            t0 = time.perf_counter()
            res = svc.query(dict(spec), timeout=600)
            answers.append(("singleton", spec, res,
                            time.perf_counter() - t0))
        t0 = time.perf_counter()
        tickets = [svc.submit(dict(spec)) for spec in PAIR]
        for spec, t in zip(PAIR, tickets):
            answers.append(("coalesced", spec, t.wait(timeout=600),
                            time.perf_counter() - t0))
        snap = svc.stats_snapshot()
    for kind, spec, res, latency in answers:
        errs, identical = compare_tables(res.table, direct(spec))
        worst = max(errs.values())
        info(f"B/{kind}: {res.meta['n_scenarios']} scenarios, latency "
             f"{latency:.3f}s (compile included), coalesced_queries "
             f"{res.meta['qos']['coalesced_queries']}; equal to direct "
             f"sweep(): bit-for-bit {identical}, max rel err {worst:.3e}")
        check(worst <= AGREEMENT_REL,
              f"B/{kind}: served answer differs from sweep() by {worst:.3e}")
        if kind == "coalesced":
            check(res.meta["qos"]["coalesced_queries"] == len(PAIR),
                  "B: the pair was not coalesced into one kernel call")
    check(snap["n_errors"] == 0, f"B: service errors {snap['n_errors']}")
    info(f"B/service: {snap['n_queries']} queries in "
         f"{snap['kernel_calls']} kernel calls")


# ----------------------------------------------------------------------
# Phase C: the measured train step
# ----------------------------------------------------------------------
def chip_geometry(n_devices: int):
    from repro.measure.run import CHIP_GEOMETRY

    return dataclasses.replace(CHIP_GEOMETRY, num_layers=NUM_LAYERS,
                               seq_len=SEQ_LEN, batch_per_gpu=2,
                               n_devices=n_devices, repeats=2,
                               step_iters=3)


def phase_c() -> None:
    from repro.configs import get_config
    from repro.core.scenarios import ScenarioGrid
    from repro.core.sweep import sweep
    from repro.core.workloads import clear_workload_cache
    from repro.measure.run import measured_config, run_measurement

    geometry = chip_geometry(1)
    full, cfg = get_config(ARCH), measured_config(ARCH, geometry)
    info(f"C/config: {cfg.name} d_model {cfg.d_model}, heads "
         f"{cfg.num_heads}/{cfg.kv_heads} kv, d_ff {cfg.d_ff}, vocab "
         f"{cfg.vocab_size}, qkv_bias {cfg.qkv_bias}, dtype "
         f"{cfg.dtype.__name__}; cut: num_layers {full.num_layers} -> "
         f"{cfg.num_layers} (depth only); seq {SEQ_LEN}, batch 2, 1 chip")
    out = OUT_DIR / "measure"
    shutil.rmtree(out, ignore_errors=True)
    t0 = time.perf_counter()
    doc = run_measurement(ARCH, out, geometry)
    info(f"C/measure: {time.perf_counter() - t0:.1f}s incl. compiles; "
         f"depths {doc['depths']}; step s/iter {doc['policy_times_s']}; "
         f"losses {doc['policy_losses']}; t_update {doc['t_update_s']:.5f}"
         f"s; segments {doc['segments']}; trace {doc['trace_path']}")
    for pol, loss in doc["policy_losses"].items():
        check(math.isfinite(loss), f"C/{pol}: loss {loss}")
        calls = doc["collective_stats"][pol]["pallas_calls"]
        info(f"C/{pol}: tpu_custom_call in step HLO: {calls}")
        check(calls > 0, f"C/{pol}: attention did not run the Pallas kernel")
    check(doc["depths"] == [2, 4], f"C: segmentation depths {doc['depths']}")

    # the trace is a jax: workload now — sweep it
    os.environ["REPRO_MEASURE_DIR"] = str(out)
    clear_workload_cache()
    grid = ScenarioGrid(workloads=(f"jax:{ARCH}",),
                        clusters=("v100-nvlink-ib", "tpu-v5e-pod"),
                        worker_counts=(1, 4, 16),
                        policies=("cntk", "caffe-mpi", "bucketed-25mb"))
    res = sweep(grid, backend="jax")
    t_iter = res.columns["iteration_time_s"]
    info(f"C/sweep: jax:{ARCH} over {len(res)} scenarios, iteration "
         f"time {t_iter.min():.5f}..{t_iter.max():.5f}s")
    check(len(res) == len(grid) and all(math.isfinite(t) and t > 0
                                        for t in t_iter),
          "C: the measured trace did not sweep to finite times")


# ----------------------------------------------------------------------
# Four chips: scenario-axis sharding and the data-parallel step
# ----------------------------------------------------------------------
def four_chip_sweep() -> None:
    import jax
    import numpy as np

    from repro.core.batched_jax import JaxGridEvaluator
    from repro.core.scenarios import frontier_grid
    from repro.launch.mesh import make_dp_mesh

    grid = frontier_grid()
    cols = {}
    for label, mesh in (("auto", None), ("one-device", make_dp_mesh(1))):
        jev = JaxGridEvaluator(grid, mesh=mesh)
        t0 = time.perf_counter()
        jev.columns()
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        cols[label] = jev.columns()
        warm_s = time.perf_counter() - t0
        n_dev = 1 if jev.mesh is None else jev.mesh.devices.size
        info(f"A4/{label}: {len(grid)} scenarios on {n_dev} device(s); "
             f"set-up {compile_s:.2f}s; warm kernel {warm_s:.4f}s = "
             f"{len(grid) / warm_s:,.0f} scenarios/s")
        if label == "auto":
            check(n_dev == len(jax.devices()),
                  f"A4: auto mesh spans {n_dev} devices")
    equal = {k: bool(np.array_equal(cols["auto"][k], cols["one-device"][k]))
             for k in cols["auto"]}
    info(f"A4/columns equal (auto mesh vs one device): {equal}")
    check(all(equal.values()), "A4: sharded columns differ from one device")


def four_chip_step(n_chips: int) -> None:
    import jax
    import numpy as np

    from repro.comm.ddp import make_ddp_train_step
    from repro.launch import hlo as hlo_mod
    from repro.launch.mesh import make_dp_mesh
    from repro.measure.calibrate import expected_collective_bytes
    from repro.measure.harness import MEASURED_SYNC_POLICIES
    from repro.measure.run import measured_config
    from repro.models import transformer as T
    from repro.optim.sgd import sgd

    cfg = measured_config(ARCH, chip_geometry(n_chips))
    opt = sgd(lr=1e-2, momentum=0.9)
    batch = {
        "tokens": jax.random.randint(jax.random.PRNGKey(0),
                                     (n_chips, SEQ_LEN), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(1),
                                     (n_chips, SEQ_LEN), 0, cfg.vocab_size),
    }

    def sq_norm(leaves):
        return sum(float(np.sum(np.square(x, dtype=np.float64)))
                   for x in leaves)

    def sq_dist(leaves_a, leaves_b):
        return sum(float(np.sum(np.square(a - b, dtype=np.float64)))
                   for a, b in zip(leaves_a, leaves_b))

    for pol in MEASURED_SYNC_POLICIES:
        runs = {}
        for n in (n_chips, 1):
            step = make_ddp_train_step(cfg, opt, make_dp_mesh(n),
                                       sync_policy=pol)
            params = T.init_lm(cfg, jax.random.PRNGKey(3))
            state = opt.init(params)
            t0 = time.perf_counter()
            compiled = step.lower(params, state, batch).compile()
            compile_s = time.perf_counter() - t0
            hlo = compiled.as_text()
            calls = hlo.count('custom_call_target="tpu_custom_call"')
            new_params, state, m = compiled(params, state, batch)
            # first momentum step: the f32 momentum IS the mean gradient;
            # it goes to the host, so the next run has the whole chip
            runs[n] = (float(m["loss"]),
                       jax.device_get(jax.tree_util.tree_leaves(state["mom"])))
            info(f"C4/{pol}: {n} chip(s), {n_chips // n} sequence(s) per "
                 f"chip; compile {compile_s:.1f}s; loss {runs[n][0]:.6f}; "
                 f"tpu_custom_call {calls}")
            if n > 1:
                got = hlo_mod.collective_stats(
                    hlo, loop_trip_count=cfg.num_units).total_bytes
                want = expected_collective_bytes(cfg, pol)
                info(f"C4/{pol}: HLO collective bytes {got:.0f}, payload "
                     f"accounting {want:.0f}")
                check(got == want, f"C4/{pol}: collective bytes {got} != "
                                   f"{want}")
            del params, new_params, state, compiled
        (loss_n, g_n), (loss_1, g_1) = runs[n_chips], runs[1]
        loss_rel = abs(loss_n - loss_1) / abs(loss_1)
        grad_rel = (sq_dist(g_n, g_1) / sq_norm(g_1)) ** 0.5
        info(f"C4/{pol}: {n_chips} chips vs 1 chip: loss rel err "
             f"{loss_rel:.3e}, gradient rel err (L2) {grad_rel:.3e}")
        check(loss_rel <= BF16_REL and grad_rel <= BF16_REL,
              f"C4/{pol}: beyond bf16 tolerance {BF16_REL}")
        del runs


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: phases A-C on one chip; 4: only the paths "
                         "that span four chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"error: no TPU: jax found {dev.platform!r} devices; "
              f"chip_smoke.py runs only on a TPU", file=sys.stderr)
        return 1
    count = len(jax.devices())
    if count < args.chips:
        print(f"error: --chips {args.chips} but jax sees {count} chip(s)",
              file=sys.stderr)
        return 1
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from repro.launch.compile_cache import enable_compilation_cache

    info(f"device: {dev.platform} / {dev.device_kind} x {count}; "
         f"compile cache {enable_compilation_cache()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        phases = (("A", phase_a), ("B", phase_b), ("C", phase_c))
    else:
        phases = (("A4", four_chip_sweep),
                  ("C4", lambda: four_chip_step(args.chips)))
    for name, phase in phases:
        t = time.perf_counter()
        phase()
        info(f"phase {name} passed in {time.perf_counter() - t:.1f}s")
    info(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
