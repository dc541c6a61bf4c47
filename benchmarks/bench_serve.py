"""Sweep service throughput: warm-cache queries vs cold one-shot CLI,
single vs multi-client qps, and coalesced vs uncoalesced serving.

    PYTHONPATH=src python -m benchmarks.bench_serve
    PYTHONPATH=src python -m benchmarks.bench_serve --smoke \
        --assert-serve-floor 5

Prints the shared ``name,us_per_call,derived`` CSV rows and writes
``BENCH_serve.json``:

* ``cold_vs_warm`` — first-query latency on a cold server (workload
  tables + grid-structure memos built on demand) vs the warm median
  for the same query: the value of process-lifetime caches.
* ``clients1`` / ``clients8`` — sequential and 8-thread closed-loop
  qps with p50/p95 latency over the same warm query.
* ``coalescing`` — the 8-client load against a micro-batching server
  (4 ms window) vs a ``window=0`` server, with each server's measured
  coalesce factor.
* ``warm_vs_cli`` — the acceptance gate: median warm query latency vs
  a cold one-shot ``python -m repro.launch.sweep`` subprocess running
  the same frontier slice.  ``--assert-serve-floor R`` fails the run
  unless the server is at least ``R``x faster; CI pins ``R = 5``.

All measurements run the server in-process on a loopback port; the CLI
comparison spawns a real subprocess — before any server starts, so
parent and child never hold jax at once — so it pays genuine import +
table-build + kernel-warm-up cost, exactly like a user running the CLI
once.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

import numpy as np

from benchmarks.common import row

#: The repeated what-if query of the benchmark: one frontier slice
#: (2880 scenarios — every policy/collective/interconnect/failure
#: combination for resnet50 at 8 workers).
QUERY = {"grid": "frontier", "workloads": ["resnet50"], "workers": [8]}


def _post(port: int, doc: dict) -> tuple[list[dict], float]:
    """One /query round trip: parsed NDJSON lines + wall latency."""
    req = urllib.request.Request(f"http://127.0.0.1:{port}/query",
                                 data=json.dumps(doc).encode(),
                                 method="POST")
    t0 = time.perf_counter()
    with urllib.request.urlopen(req) as resp:
        lines = [json.loads(line) for line in resp]
    return lines, time.perf_counter() - t0


def _stats(port: int) -> dict:
    with urllib.request.urlopen(f"http://127.0.0.1:{port}/stats") as r:
        return json.loads(r.read())


def _start_server(window_s: float):
    from repro.launch.serve_sweep import make_server

    srv = make_server(port=0, window_s=window_s)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, srv.server_address[1]


def _stop_server(srv) -> None:
    srv.shutdown()
    srv.server_close()
    srv.service.close()


def _pcts(latencies: list[float]) -> dict:
    a = np.sort(np.asarray(latencies))
    return {"p50_ms": float(np.quantile(a, 0.50)) * 1e3,
            "p95_ms": float(np.quantile(a, 0.95)) * 1e3}


def _closed_loop(port: int, clients: int, per_client: int) -> dict:
    """``clients`` threads, each issuing ``per_client`` back-to-back
    queries; aggregate qps over the wall window + latency percentiles."""
    lats: list[list[float]] = [[] for _ in range(clients)]

    def drive(i: int) -> None:
        for _ in range(per_client):
            _, dt = _post(port, QUERY)
            lats[i].append(dt)

    threads = [threading.Thread(target=drive, args=(i,))
               for i in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = [dt for ls in lats for dt in ls]
    return {"clients": clients, "queries": len(flat), "wall_s": wall,
            "qps": len(flat) / wall, **_pcts(flat)}


def _time_cli_once() -> float:
    """One cold ``python -m repro.launch.sweep`` subprocess running the
    benchmark query (imports + tables + kernel warm-up + sweep)."""
    with tempfile.NamedTemporaryFile(suffix=".json") as tmp:
        env = dict(os.environ)
        src = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "src")
        env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
        cmd = [sys.executable, "-m", "repro.launch.sweep",
               "--grid", "frontier", "--workloads", "resnet50",
               "--workers", "8", "--json", tmp.name]
        t0 = time.perf_counter()
        subprocess.run(cmd, check=True, env=env,
                       stdout=subprocess.DEVNULL)
        return time.perf_counter() - t0


def run(smoke: bool = False, json_path: str = "BENCH_serve.json",
        assert_floor: float = 0.0) -> dict:
    warm_reps = 10 if smoke else 50
    per_client = 5 if smoke else 25
    report: dict = {"smoke": smoke, "query": QUERY}
    # the one-shot CLI child runs first, before this process starts a
    # server: a parent that holds a device would contend with it
    cli_s = _time_cli_once()

    # -- cold vs warm first query (this server is the process's first:
    # nothing has resolved a workload or built an evaluator yet) ------
    srv, port = _start_server(window_s=0.004)
    lines, cold_s = _post(port, QUERY)
    probe = lines[-1]["qos"]["cache"]
    warm_lat = [_post(port, QUERY)[1] for _ in range(warm_reps)]
    warm_s = float(np.median(warm_lat))
    report["cold_vs_warm"] = {
        "cold_first_query_s": cold_s, "warm_median_s": warm_s,
        "speedup": cold_s / warm_s if warm_s else 0.0,
        "cold_cache_probe": probe,
        "n_scenarios": lines[0]["n_scenarios"]}
    row("serve_cold_first_query", cold_s * 1e6,
        f"{lines[0]['n_scenarios']} scenarios, caches cold")
    row("serve_warm_query", warm_s * 1e6,
        f"warm median ({report['cold_vs_warm']['speedup']:.1f}x cold)")

    # -- closed-loop qps ----------------------------------------------
    report["clients1"] = _closed_loop(port, 1, per_client * 8)
    report["clients8"] = _closed_loop(port, 8, per_client)
    for key in ("clients1", "clients8"):
        c = report[key]
        row(f"serve_{key}", 1e6 / c["qps"],
            f"{c['qps']:.1f} qps, p50 {c['p50_ms']:.1f} ms, "
            f"p95 {c['p95_ms']:.1f} ms")
    coalesced = _closed_loop(port, 8, per_client)
    coalesced["coalesce_factor"] = _stats(port)["coalesce_factor"]
    _stop_server(srv)

    # -- coalesced vs uncoalesced -------------------------------------
    srv0, port0 = _start_server(window_s=0.0)
    _post(port0, QUERY)                              # warm it
    uncoalesced = _closed_loop(port0, 8, per_client)
    uncoalesced["coalesce_factor"] = _stats(port0)["coalesce_factor"]
    _stop_server(srv0)
    report["coalescing"] = {
        "coalesced": coalesced, "uncoalesced": uncoalesced,
        "qps_ratio": coalesced["qps"] / uncoalesced["qps"]}
    row("serve_coalesced_8c", 1e6 / coalesced["qps"],
        f"{coalesced['qps']:.1f} qps at coalesce factor "
        f"{coalesced['coalesce_factor']:.2f}")
    row("serve_uncoalesced_8c", 1e6 / uncoalesced["qps"],
        f"{uncoalesced['qps']:.1f} qps at window 0")

    # -- warm server vs cold one-shot CLI (the acceptance gate) -------
    speedup = cli_s / warm_s if warm_s else 0.0
    report["warm_vs_cli"] = {"cli_one_shot_s": cli_s,
                             "warm_query_s": warm_s,
                             "speedup": speedup,
                             "floor": assert_floor}
    row("serve_vs_cli_one_shot", cli_s * 1e6,
        f"cold CLI; warm server query is {speedup:.1f}x faster")

    if json_path:
        with open(json_path, "w") as f:
            json.dump(report, f, indent=2)
        print(f"# wrote {json_path}", flush=True)
    if assert_floor and speedup < assert_floor:
        raise AssertionError(
            f"warm query speedup {speedup:.2f}x is below the "
            f"--assert-serve-floor {assert_floor}x")
    return report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced repeat counts (CI mode)")
    ap.add_argument("--json", default="BENCH_serve.json", metavar="PATH",
                    help="output JSON path ('' to skip)")
    ap.add_argument("--assert-serve-floor", type=float, default=0.0,
                    metavar="R",
                    help="fail unless warm queries beat the one-shot "
                         "CLI by at least Rx")
    args = ap.parse_args(argv)
    print("name,us_per_call,derived")
    run(smoke=args.smoke, json_path=args.json,
        assert_floor=args.assert_serve_floor)
    return 0


if __name__ == "__main__":
    sys.exit(main())
