"""Scenario-sweep CLI: evaluate a grid of S-SGD what-if scenarios and
emit a tidy results table.

    PYTHONPATH=src python -m repro.launch.sweep
    PYTHONPATH=src python -m repro.launch.sweep --grid mixed
    PYTHONPATH=src python -m repro.launch.sweep --grid frontier \\
        --stream --csv /tmp/frontier.csv
    PYTHONPATH=src python -m repro.launch.sweep \\
        --workloads cnn:resnet50,trace:alexnet-k80,llm:gemma3-1b \\
        --clusters v100-nvlink-ib \\
        --workers 4,8,16,32 --policies caffe-mpi,bucketed-25mb \\
        --collectives ring,tree,hierarchical --csv /tmp/sweep.csv
    PYTHONPATH=src python -m repro.launch.sweep \\
        --het none,het:1x0.5+3x1.0 --stragglers none,lognormal:0.2x1000 \\
        --seed 7 --sort t_p99_s
    PYTHONPATH=src python -m repro.launch.sweep \\
        --workers 8 --sync-k none,6 \\
        --faults none,fail:0.01@restart2.5x1000 --sort t_p99_s

Workloads resolve through the pluggable registry
(``repro.core.workloads``): bare paper CNN names or ``cnn:<name>``,
``trace:<bundled-name-or-file-path>``, ``llm:<arch>``, and measured
``jax:<name-or-path>`` workloads harvested from the repo's own
executed train steps (``python -m repro.measure --arch <id>``) — see
``--list-workloads``.  Axis values are comma-separated;
``--interconnects`` accepts preset names from
``repro.core.hardware.INTERCONNECT_PRESETS``, scaled what-ifs
(``ib-100g@bw2@lat0.25``) and ``default`` (keep the cluster's own
links).  The default grid is 540 scenarios on the batched analytical
fast path (milliseconds end to end); ``--grid mixed`` spans all three
providers (1620 scenarios); ``--grid frontier`` is the 51 840-scenario
bandwidth x latency x bucket-size x priority design-space study
(schedule-dependent policies ride the batched bucket-timeline path, so
the whole grid evaluates in tens of milliseconds) — pair it with
``--stream``
to write CSV/JSON incrementally instead of buffering every row.
"""
from __future__ import annotations

import argparse
import sys

from repro.core.hardware import COLLECTIVE_ALGORITHMS, INTERCONNECT_PRESETS
from repro.core.scenarios import grid_from_spec
from repro.core.sweep import COLUMNS, DEFAULT_CHUNK, stream, sweep
from repro.core.workloads import known_workloads


def _csv_list(text: str) -> list[str]:
    return [t.strip() for t in text.split(",") if t.strip()]


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.launch.sweep",
        description="Batched what-if sweep over the S-SGD DAG model.")
    p.add_argument("--grid", choices=("default", "mixed", "frontier"),
                   default="default",
                   help="base grid: 'default' (paper CNNs, 540 scenarios), "
                        "'mixed' (cnn:/trace:/llm: providers, 1620) or "
                        "'frontier' (bandwidth x latency x bucket-size x "
                        "priority what-ifs, 51840); other axis flags "
                        "override any of them")
    p.add_argument("--workloads", type=_csv_list, default=None,
                   help="comma-separated workload names: bare CNNs "
                        "(alexnet,googlenet,resnet50), cnn:<name>, "
                        "trace:<bundled-or-path>, llm:<arch>, "
                        "jax:<measured-name-or-path> "
                        "(see --list-workloads; measure with "
                        "`python -m repro.measure`)")
    p.add_argument("--list-workloads", action="store_true",
                   help="print every registered workload name and exit")
    p.add_argument("--clusters", type=_csv_list, default=None,
                   help="comma-separated cluster names")
    p.add_argument("--workers", type=_csv_list, default=None,
                   help="comma-separated worker counts, e.g. 1,4,16,64")
    p.add_argument("--policies", type=_csv_list, default=None,
                   help="comma-separated policy names (see repro.core.policies)")
    p.add_argument("--collectives", type=_csv_list, default=None,
                   help=f"comma-separated algorithms {COLLECTIVE_ALGORITHMS}")
    p.add_argument("--interconnects", type=_csv_list, default=None,
                   help="comma-separated presets "
                        f"({', '.join(sorted(INTERCONNECT_PRESETS))}) "
                        "and/or 'default'")
    p.add_argument("--het", type=_csv_list, default=None,
                   help="comma-separated heterogeneity profiles: 'none' "
                        "and/or 'het:<slots>' specs, e.g. "
                        "het:1x0.5+3x1.0 (one half-speed worker per 4), "
                        "het:2x1.0@bw0.5 (half link bandwidth); see "
                        "repro.core.het")
    p.add_argument("--stragglers", type=_csv_list, default=None,
                   help="comma-separated straggler models: 'none' and/or "
                        "'<dist>:<scale>[x<draws>]' with dist lognormal|exp, "
                        "e.g. lognormal:0.2x1000 — Monte Carlo tails land "
                        "in t_mean_s/t_p95_s/t_p99_s")
    p.add_argument("--sync-k", type=_csv_list, default=None,
                   help="comma-separated K-of-N partial-sync thresholds: "
                        "'none'/'0' (full sync) and/or positive K — each "
                        "iteration waits for the first K of N gradients "
                        "(K is clamped to the worker count; backup "
                        "workers = N - K)")
    p.add_argument("--faults", type=_csv_list, default=None,
                   help="comma-separated fault models: 'none' and/or "
                        "'fail:<p>[@restart<T>][x<draws>]' — each worker "
                        "crashes with probability p per iteration and "
                        "pays a T-second checkpoint restore (default "
                        "restart 5s), e.g. fail:0.01@restart2.5x1000; "
                        "Monte Carlo tails land in t_mean_s/t_p95_s/"
                        "t_p99_s")
    p.add_argument("--seed", type=int, default=0,
                   help="seed for the straggler Monte Carlo draws "
                        "(default 0; draws are keyed by (spec, workers, "
                        "seed), so results are reproducible across "
                        "backends, --jobs and chunking)")
    p.add_argument("--batch-per-gpu", type=int, default=None,
                   help="override the workload's per-GPU batch size")
    p.add_argument("--force-simulator", action="store_true",
                   help="run every scenario through the event-driven "
                        "simulator (slow; for validation)")
    p.add_argument("--per-scenario", action="store_true",
                   help="pin closed-form scenarios to the per-scenario "
                        "reference path instead of the batched kernel "
                        "(slow; the agreement oracle)")
    p.add_argument("--backend", choices=("numpy", "jax"), default="numpy",
                   help="batched kernel backend: 'numpy' (default, the "
                        "oracle) or 'jax' (jit+vmap kernels, sharded over "
                        "available devices; incompatible with "
                        "--per-scenario and --force-simulator)")
    p.add_argument("--jobs", type=int, default=None, metavar="N",
                   help="shard a grid sweep across N worker processes "
                        "(-1 = one per core; output is bit-identical to "
                        "serial, in the same order); with --backend jax "
                        "shards over the device mesh instead")
    p.add_argument("--chunk", type=int, default=None, metavar="N",
                   help="scenarios per evaluation chunk (default "
                        f"{DEFAULT_CHUNK}): the streaming buffer unit, "
                        "and the minimum shard size under --jobs")
    p.add_argument("--stream", action="store_true",
                   help="stream rows straight to --csv/--json without "
                        "buffering the table (huge grids); skips the "
                        "printed table")
    p.add_argument("--sort", default="samples_per_sec",
                   help="result column to sort by (descending)")
    p.add_argument("--top", type=int, default=20,
                   help="print only the best N rows (0 = all)")
    p.add_argument("--csv", default=None, metavar="PATH",
                   help="also write the full table as CSV")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="also write the full table (plus sweep metadata) "
                        "as JSON")
    return p


def grid_from_args(args: argparse.Namespace):
    """The chosen base grid with any CLI-provided axes substituted in.

    Delegates to :func:`repro.core.scenarios.grid_from_spec` — the
    CLI's flags and the sweep service's JSON query documents
    (:mod:`repro.core.service`) share one axis vocabulary and one
    parser, so a spec this CLI exits 2 on is exactly one the server
    rejects with a structured error, and vice versa."""
    spec: dict = {"grid": args.grid}
    for key, val in (("workloads", args.workloads),
                     ("clusters", args.clusters),
                     ("workers", args.workers),
                     ("policies", args.policies),
                     ("collectives", args.collectives),
                     ("interconnects", args.interconnects),
                     ("het", args.het),
                     ("stragglers", args.stragglers),
                     ("sync_k", args.sync_k),
                     ("faults", args.faults)):
        if val:
            spec[key] = val
    if args.batch_per_gpu is not None:
        spec["batch_per_gpu"] = args.batch_per_gpu
    return grid_from_spec(spec)


def main(argv: list[str] | None = None) -> int:
    parser = build_parser()
    args = parser.parse_args(argv)
    if args.list_workloads:
        for name in known_workloads():
            print(name)
        return 0
    try:
        grid = grid_from_args(args)
        grid.validate_axes()           # validate axis values up front
    except (ValueError, KeyError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if args.sort and args.sort not in COLUMNS:
        print(f"error: unknown --sort column {args.sort!r}; "
              f"one of {', '.join(COLUMNS)}", file=sys.stderr)
        return 2
    if args.stream and not (args.csv or args.json):
        print("error: --stream requires --csv and/or --json",
              file=sys.stderr)
        return 2
    if args.backend == "jax":
        from repro.launch.compile_cache import enable_compilation_cache
        enable_compilation_cache()
    if args.backend == "jax" and args.per_scenario:
        print("error: --backend jax is the batched kernel; --per-scenario "
              "pins the per-scenario NumPy reference paths (drop one)",
              file=sys.stderr)
        return 2
    if args.backend == "jax" and args.force_simulator:
        print("error: --backend jax has no event-driven simulator; "
              "--force-simulator needs --backend numpy",
              file=sys.stderr)
        return 2
    print(f"sweep: {len(grid)} scenarios "
          f"({len(grid.workloads)} workloads x {len(grid.clusters)} clusters "
          f"x {len(grid.worker_counts)} sizes x {len(grid.policies)} policies "
          f"x {len(grid.collectives)} collectives "
          f"x {len(grid.interconnects)} interconnects "
          f"x {len(grid.het_profiles)} het x {len(grid.stragglers)} "
          f"stragglers x {len(grid.sync_ks)} sync-k "
          f"x {len(grid.faults)} faults)")
    if args.stream:
        summary = stream(grid, csv_path=args.csv, json_path=args.json,
                         force_simulator=args.force_simulator,
                         batched=not args.per_scenario,
                         backend=args.backend, jobs=args.jobs,
                         chunk=args.chunk or DEFAULT_CHUNK,
                         seed=args.seed)
        dests = ", ".join(p for p in (args.csv, args.json) if p)
        print(f"streamed {summary['n_scenarios']} rows to {dests} "
              f"in {summary['elapsed_s']:.2f}s "
              f"({summary['scenarios_per_sec']:,.0f}/s; "
              f"{summary['n_analytical']} analytical, "
              f"{summary['n_timeline']} timeline, "
              f"{summary['n_simulated']} simulated)")
        return 0
    result = sweep(grid, force_simulator=args.force_simulator,
                   batched=not args.per_scenario, backend=args.backend,
                   jobs=args.jobs, chunk=args.chunk, seed=args.seed)
    print(f"evaluated in {result.elapsed_s:.2f}s "
          f"({result.scenarios_per_sec:,.0f}/s; "
          f"{result.n_analytical} analytical, "
          f"{result.n_timeline} timeline, "
          f"{result.n_simulated} simulated)")

    rows = result.sorted_by(args.sort) if args.sort else result.rows
    limit = args.top if args.top and args.top > 0 else None
    print()
    print(result.format_table(rows, limit=limit))
    if limit is not None and len(rows) > limit:
        print(f"... {len(rows) - limit} more rows "
              f"(use --top 0 for all, --csv for the full table)")
    if args.csv:
        result.to_csv(args.csv)
        print(f"\nwrote {len(result)} rows to {args.csv}")
    if args.json:
        result.to_json(args.json)
        print(f"\nwrote {len(result)} rows to {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
