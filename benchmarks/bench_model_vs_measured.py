"""Model vs. measurement, on this repo's own stack (paper Fig. 4 / §V-D
closed-loop): measure real jax train steps under each gradient-sync
policy, predict the same iteration times from the harvested per-layer
trace via the DAG model, and report the error.

    PYTHONPATH=src python -m benchmarks.bench_model_vs_measured --smoke \\
        --json BENCH_calibration.json --assert-error-ceiling 200

Per architecture (two by default), the measurement subprocess
(:func:`repro.measure.run.measure_in_subprocess`: a CPU-platform child
on forced host devices, so it never contends for a chip this process
may hold; on a chip host, ``python -m repro.measure`` measures the
chips in process instead) produces:

* measured seconds/iteration for ``at_end`` / ``wfbp`` / ``bucketed``;
* a per-layer trace (scan-segmented fwd/bwd, measured collectives);
* an alpha-beta fit of the host's all-reduce and the HLO collective
  byte cross-check.

The parent then predicts each policy's iteration time with
:func:`repro.core.predictor.predict_sync_policy` over the measured
costs, records per-policy error, registers the traces as ``jax:``
workloads and sweeps them through the batched engine (closed-form
*and* bucket-timeline paths) — everything lands in
``BENCH_calibration.json``.  ``--assert-error-ceiling PCT`` turns the
maximum per-policy error into a CI gate (host-CPU wall clocks are
noisy; the ceiling guards against structural model breakage, not
single-digit accuracy).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from benchmarks.common import row
from repro.comm.sync import DEFAULT_BUCKET_BYTES
from repro.core.predictor import predict_sync_policy
from repro.core.scenarios import ScenarioGrid
from repro.core.sweep import sweep
from repro.core.workloads import (clear_workload_cache, known_workloads,
                                  resolve_workload)
from repro.measure.calibrate import comm_scale_from_fit
from repro.measure.run import (MEASURABLE_ARCHS, Geometry, SMOKE_GEOMETRY,
                               default_out_dir, measure_in_subprocess)
from repro.traces.format import read_trace

DEFAULT_ARCHS = ("qwen1.5-4b", "gemma3-1b")


def predict_policies(doc: dict, trace_path: str) -> dict[str, float]:
    """Model predictions (seconds/iteration) for every measured policy,
    from the harvested trace + calibration fit alone."""
    trace = read_trace(trace_path)
    costs = trace.to_iteration_costs(t_u=doc["t_update_s"])
    fit = doc["allreduce_fit"]
    comm_scale = comm_scale_from_fit(fit["latency_s"],
                                     fit["bandwidth_bytes_per_s"])
    # the modeled bucketed policy uses the very threshold the step was
    # lowered with (one shared constant, repro.comm.sync)
    return {
        pol: predict_sync_policy(costs, doc["n_devices"], pol,
                                 comm_scale=comm_scale,
                                 bucket_bytes=DEFAULT_BUCKET_BYTES)
        for pol in doc["policy_times_s"]
    }


def sweep_measured_workloads(archs: list[str]) -> dict:
    """Sweep the freshly measured ``jax:`` workloads through the
    batched engine — closed-form policies ride the analytical path,
    bucketed/priority the bucket-timeline path — and return the row
    accounting (the acceptance check that lowered models are now
    first-class sweep citizens)."""
    grid = ScenarioGrid(
        workloads=tuple(f"jax:{a}" for a in archs),
        clusters=("k80-pcie-10gbe", "v100-nvlink-ib"),
        worker_counts=(2, 8, 32),
        policies=("cntk", "caffe-mpi", "bucketed-25mb", "priority"),
        collectives=("ring",),
    )
    res = sweep(grid)
    return {
        "n_scenarios": len(res),
        "n_analytical": res.n_analytical,
        "n_timeline": res.n_timeline,
        "n_simulated": res.n_simulated,
        "elapsed_s": res.elapsed_s,
    }


def run(archs=None, geometry: Geometry | None = None,
        out_dir: str | None = None, smoke: bool = True) -> dict:
    archs = list(archs or DEFAULT_ARCHS)
    geometry = geometry or SMOKE_GEOMETRY
    out_dir = out_dir or default_out_dir()
    doc: dict = {
        "smoke": smoke,
        "n_devices": geometry.n_devices,
        "measure_dir": out_dir,
        "policies": None,
        "archs": {},
    }
    t0 = time.time()
    max_err = 0.0
    for arch in archs:
        rec = measure_in_subprocess(arch, out_dir=out_dir,
                                    geometry=geometry)
        predicted = predict_policies(rec, rec["trace_path"])
        policies = sorted(predicted)
        doc["policies"] = policies
        entry = {
            "config": rec["config"],
            "measured_s": rec["policy_times_s"],
            "predicted_s": predicted,
            "error_pct": {},
            "t_update_s": rec["t_update_s"],
            "allreduce_fit": rec["allreduce_fit"],
            "bytes_crosscheck": rec["bytes_crosscheck"],
            "trace_path": rec["trace_path"],
        }
        for pol in policies:
            meas = rec["policy_times_s"][pol]
            pred = predicted[pol]
            err = abs(pred - meas) / meas * 100 if meas else float("inf")
            entry["error_pct"][pol] = err
            max_err = max(max_err, err)
            row(f"calibration/{arch}/{pol}", 0.0,
                f"measured_s={meas:.5f};predicted_s={pred:.5f};"
                f"err_pct={err:.1f}")
        for pol, c in rec["bytes_crosscheck"].items():
            row(f"calibration/{arch}/{pol}-bytes", 0.0,
                f"hlo={c['hlo_bytes']:.0f};expected={c['expected_bytes']:.0f};"
                f"rel_err={c['rel_err']:.2e}")
        doc["archs"][arch] = entry

    # the measured traces are now jax: workloads — sweep them
    os.environ["REPRO_MEASURE_DIR"] = out_dir
    clear_workload_cache()
    names = [w for w in known_workloads() if w.startswith("jax:")]
    for a in archs:
        if f"jax:{a}" not in names:
            raise RuntimeError(
                f"measured workload jax:{a} not enumerated by the "
                f"provider (measure dir {out_dir!r}, found {names})")
        resolve_workload(f"jax:{a}")
    doc["jax_workloads"] = names
    doc["sweep"] = sweep_measured_workloads(archs)
    row("calibration/jax-sweep", doc["sweep"]["elapsed_s"] * 1e6,
        f"scenarios={doc['sweep']['n_scenarios']};"
        f"analytical={doc['sweep']['n_analytical']};"
        f"timeline={doc['sweep']['n_timeline']};"
        f"simulated={doc['sweep']['n_simulated']}")
    doc["max_error_pct"] = max_err
    doc["elapsed_s"] = time.time() - t0
    return doc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny geometry (CI-sized; a couple of minutes "
                         "on two host CPU devices)")
    ap.add_argument("--archs", default=",".join(DEFAULT_ARCHS),
                    help=f"comma-separated archs from {MEASURABLE_ARCHS}")
    ap.add_argument("--devices", type=int, default=None,
                    help="DP world size (forced host devices)")
    ap.add_argument("--out-dir", default=None,
                    help="measurement directory (default: "
                         "$REPRO_MEASURE_DIR or results/measure/)")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full calibration document here")
    ap.add_argument("--assert-error-ceiling", type=float, default=None,
                    metavar="PCT",
                    help="exit 1 if any per-policy |model-measured| "
                         "error exceeds PCT percent")
    args = ap.parse_args(argv)

    archs = [a.strip() for a in args.archs.split(",") if a.strip()]
    for a in archs:
        if a not in MEASURABLE_ARCHS:
            ap.error(f"unknown/unmeasurable arch {a!r}; "
                     f"one of {MEASURABLE_ARCHS}")
    geometry = SMOKE_GEOMETRY if args.smoke else Geometry()
    if args.devices:
        import dataclasses

        geometry = dataclasses.replace(geometry, n_devices=args.devices)
    out_dir = args.out_dir or default_out_dir()

    doc = run(archs, geometry, out_dir, args.smoke)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(doc, f, indent=2)
        print(f"wrote {args.json}")
    print(f"max per-policy error: {doc['max_error_pct']:.1f}%  "
          f"(archs={','.join(archs)}; policies={doc['policies']}; "
          f"{doc['elapsed_s']:.0f}s)")
    if args.assert_error_ceiling is not None \
            and doc["max_error_pct"] > args.assert_error_ceiling:
        print(f"ERROR: max error {doc['max_error_pct']:.1f}% exceeds "
              f"ceiling {args.assert_error_ceiling:g}%", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
