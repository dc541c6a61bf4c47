"""Readings that set the limits of the check, in one process on the chip.

    python3 chipbench/control.py --workload frontier.sweep --seeds 1,2,3 --seconds 5

For each seed it runs a short window of the cell at its own load and
compares the sampled answers with the configuration's own reference
three ways: the program (the lower reading of the limit), that
reference computed in float32 and put in the program's place (the
control, whose smallest reading is the upper one), and, with
``--faults``, the program with each fault of :mod:`chipbench.faults`
planted.  The benchmark's own runs never run this; its readings are
what ``PERF.md`` sets each limit from.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path.pop(0)
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import check, faults, run  # noqa: E402


def readings(workload: str, seeds: list[int], seconds: float,
             planted: tuple = (), require_tpu: bool = True) -> list[dict]:
    spec = run.resolve(ROOT, workload)
    config, traffic = spec["config"], spec["traffic"]
    import jax

    device = run.device_info(jax, spec["cell"]["chips"], require_tpu)
    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    out = []
    for seed in seeds:
        row = {"seed": seed, "device": device}
        for label in ("program", *planted):
            undo = faults.plant(label) if label != "program" else None
            driver = spec["driver"].Driver(config, traffic, seed)
            try:
                driver.setup()
                records, _ = driver.window(seconds)
            finally:
                driver.close()
                if undo is not None:
                    undo()
            got = check.compare(spec["reference"], config, records)
            row[label] = {k: v["value"] for k, v in got.items()}
            if label == "program":
                low = check.compare(spec["reference"], config, records,
                                    control=True)
                row["control"] = {k: v["value"] for k, v in low.items()}
        print(json.dumps(row), flush=True)
        out.append(row)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--faults", action="store_true",
                    help="also read every fault of chipbench.faults")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        rows = readings(args.workload, seeds, args.seconds,
                        faults.FAULTS if args.faults else ())
    except run.NoChip as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    for label in ("program", "control", *(faults.FAULTS if args.faults
                                          else ())):
        vals = [r[label]["max_rel_err"] for r in rows]
        print(f"{label}: max_rel_err over {len(vals)} seeds "
              f"min {min(vals)!r} max {max(vals)!r}; label mismatches "
              f"{[r[label]['label_mismatches'] for r in rows]}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
