"""Sweep-as-a-service (the persistent what-if server).

Pins the service contracts:

* **Bit-identity** — a query served through the coalescer (alone or
  fused with concurrent heterogeneous queries) returns *exactly* the
  column arrays a direct :func:`repro.core.sweep.sweep` of its grid
  produces (``np.array_equal`` per column), on both backends, and the
  identity survives the HTTP NDJSON round trip (floats serialize via
  ``repr`` shortest round-trip).
* **Coalescing** — same-signature queries submitted within one batch
  window share **one** kernel call (asserted via the service's kernel
  counter); different seeds (and different padded layer depths) split
  into separate calls.
* **Cache accounting** — the first query against a fresh workload is
  a recorded miss, the repeat a hit, without the probe perturbing the
  caches it measures.
* **Robustness** — malformed queries produce structured
  :class:`repro.core.service.QueryError` / HTTP 400 documents (the
  same rejections the CLI exits 2 on, never a traceback, no
  ``scenarios_per_sec`` division by zero), and a client disconnecting
  mid-stream leaves the server serving.
* **Trailer parity** — the streamed trailer carries exactly the
  :data:`repro.core.sweep.RESULT_META_KEYS` metadata (plus ``qos``),
  key-for-key with :meth:`SweepResult.to_json`.
"""
from __future__ import annotations

import json
import socket
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.core.resulttable import COLUMNS, table_from_rows, table_len
from repro.core.scenarios import grid_from_spec
from repro.core.service import QueryError, SweepService, parse_query
from repro.core.sweep import RESULT_META_KEYS, sweep


def assert_tables_equal(got: dict, want: dict) -> None:
    """Bit-exact column equality (object label columns compare by
    value; float columns must match bit for bit)."""
    assert table_len(got) == table_len(want) > 0
    for k in COLUMNS:
        assert np.array_equal(got[k], want[k]), k


def reference(spec: dict, backend: str = "numpy"):
    grid = grid_from_spec({k: v for k, v in spec.items()
                           if k not in ("backend", "seed")})
    return sweep(grid, backend=backend, seed=spec.get("seed", 0))


# Heterogeneous same-workload queries: same padded layer depth, so all
# four share one kernel signature (seed 7).
COALESCE_SPECS = [
    {"workloads": ["resnet50"], "workers": [4, 8], "seed": 7},
    {"grid": "mixed", "workloads": ["resnet50"], "workers": [8],
     "seed": 7},
    {"workloads": ["resnet50"], "workers": [4],
     "het": ["het:1x0.5+3x1.0"], "seed": 7},
    {"workloads": ["resnet50"], "workers": [16],
     "sync_k": ["none", "3"], "seed": 7},
]


# ----------------------------------------------------------------------
# parse_query: the structured rejection surface
# ----------------------------------------------------------------------
class TestParseQuery:
    @pytest.mark.parametrize("doc,fragment", [
        ({"grid": "nope"}, "grid"),
        ({"bogus": 1}, "unknown query keys"),
        ({"backend": "tpu"}, "backend"),
        ({"seed": "x"}, "seed"),
        ({"seed": True}, "seed"),
        ({"workloads": []}, "workloads"),
        ({"workloads": ["no-such-net"]}, "workload"),
        ({"sync_k": ["-3"]}, "sync_k"),
        ({"policies": ["no-such-policy"]}, "policy"),
    ])
    def test_rejections_are_structured(self, doc, fragment):
        with pytest.raises(QueryError) as ei:
            parse_query(doc)
        assert fragment in str(ei.value)
        assert ei.value.code in ("bad-query", "empty-grid")

    def test_non_dict_rejected(self):
        with pytest.raises(QueryError):
            parse_query(["not", "a", "dict"])

    def test_defaults(self):
        q = parse_query({"workloads": ["resnet50"], "workers": [4]})
        assert (q.backend, q.seed, q.coalescable) == ("numpy", 0, True)
        assert len(q.grid) > 0

    def test_signature_carries_padded_depth(self):
        qa = parse_query({"workloads": ["resnet50"], "workers": [4]})
        qb = parse_query({"workloads": ["alexnet"], "workers": [4]})
        assert qa.signature != qb.signature
        assert qa.signature[:2] == qb.signature[:2]


# ----------------------------------------------------------------------
# SweepService: coalescing + bit-identity + QoS
# ----------------------------------------------------------------------
class TestServiceCoalescing:
    def test_singleton_bit_identity(self):
        with SweepService(window_s=0.0) as svc:
            spec = {"workloads": ["resnet50"], "workers": [4, 8],
                    "seed": 7}
            res = svc.query(dict(spec), timeout=120)
            assert_tables_equal(res.table, reference(spec).columns)

    def test_coalesced_group_bit_identity_one_kernel_call(self):
        # a long window so all four queries land in one batch
        with SweepService(window_s=0.5, max_coalesce=8) as svc:
            tickets = [svc.submit(dict(s)) for s in COALESCE_SPECS]
            results = [t.wait(timeout=120) for t in tickets]
            snap = svc.stats_snapshot()
        for spec, res in zip(COALESCE_SPECS, results):
            assert_tables_equal(res.table, reference(spec).columns)
            assert res.meta["qos"]["coalesced_queries"] == 4
        assert snap["kernel_calls"] == 1
        assert snap["coalesce_factor"] == 4.0
        assert snap["n_queries"] == 4

    def test_different_seeds_split_kernel_calls(self):
        with SweepService(window_s=0.5) as svc:
            a = svc.submit({"workloads": ["resnet50"], "workers": [4],
                            "seed": 1})
            b = svc.submit({"workloads": ["resnet50"], "workers": [4],
                            "seed": 2})
            a.wait(timeout=120), b.wait(timeout=120)
            assert svc.stats_snapshot()["kernel_calls"] == 2

    def test_mixed_depth_split_stays_bit_identical(self):
        # different padded layer depths must not share a kernel call
        # (the layer-sum reduction tree depends on the padding), and
        # each split group must still match its direct sweep exactly
        specs = [
            {"workloads": ["googlenet"], "workers": [8], "seed": 7},
            {"workloads": ["alexnet"], "workers": [2, 4], "seed": 7},
            {"workloads": ["googlenet"], "workers": [2],
             "stragglers": ["lognormal:0.2"], "seed": 7},
        ]
        with SweepService(window_s=0.5, max_coalesce=8) as svc:
            tickets = [svc.submit(dict(s)) for s in specs]
            results = [t.wait(timeout=120) for t in tickets]
            snap = svc.stats_snapshot()
        for spec, res in zip(specs, results):
            assert_tables_equal(res.table, reference(spec).columns)
        assert snap["kernel_calls"] == 2     # googlenet pair + alexnet

    def test_jax_coalesced_bit_identity(self):
        specs = COALESCE_SPECS[:2]
        with SweepService(window_s=0.5) as svc:
            tickets = [svc.submit({**s, "backend": "jax"})
                       for s in specs]
            results = [t.wait(timeout=300) for t in tickets]
            snap = svc.stats_snapshot()
        for spec, res in zip(specs, results):
            assert_tables_equal(res.table,
                                reference(spec, backend="jax").columns)
        assert snap["kernel_calls"] == 1

    def test_concurrent_submitters_all_bit_identical(self):
        refs = [reference(s).columns for s in COALESCE_SPECS]
        with SweepService(window_s=0.05) as svc:
            out = [None] * len(COALESCE_SPECS)

            def run(i, spec):
                out[i] = svc.query(dict(spec), timeout=120)

            threads = [threading.Thread(target=run, args=(i, s))
                       for i, s in enumerate(COALESCE_SPECS)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        for res, ref in zip(out, refs):
            assert_tables_equal(res.table, ref)


class TestServiceAccounting:
    def test_cache_miss_then_hit(self, monkeypatch):
        # a private table memo so process history can't pre-warm it
        monkeypatch.setattr("repro.core.workloads._TABLES", {})
        monkeypatch.setattr("repro.core.batched._EVALUATOR_MEMO", {})
        spec = {"workloads": ["alexnet"], "workers": [2]}
        with SweepService(window_s=0.0) as svc:
            first = svc.query(dict(spec), timeout=120)
            second = svc.query(dict(spec), timeout=120)
            snap = svc.stats_snapshot()
        assert first.meta["qos"]["cache"]["workload_tables"] == "miss"
        assert second.meta["qos"]["cache"]["workload_tables"] == "hit"
        assert first.meta["qos"]["cache"]["grid_structure"] == "miss"
        assert second.meta["qos"]["cache"]["grid_structure"] == "hit"
        for name in ("workload_tables", "grid_structure"):
            assert snap["cache"][name] == {"hits": 1, "misses": 1,
                                           "hit_rate": 0.5}

    def test_trailer_meta_matches_to_json_keys(self):
        spec = {"workloads": ["resnet50"], "workers": [4], "seed": 7}
        with SweepService(window_s=0.0) as svc:
            res = svc.query(dict(spec), timeout=120)
        assert set(res.meta) == set(RESULT_META_KEYS) | {"qos"}
        doc = json.loads(reference(spec).to_json())
        assert set(doc) - {"columns", "rows"} == set(RESULT_META_KEYS)
        for k in ("n_scenarios", "n_analytical", "n_timeline",
                  "n_simulated", "backend"):
            assert res.meta[k] == doc[k], k

    def test_stats_snapshot_shape(self):
        with SweepService(window_s=0.0) as svc:
            svc.query({"workloads": ["resnet50"], "workers": [4]},
                      timeout=120)
            snap = svc.stats_snapshot()
        assert snap["n_queries"] == 1 and snap["n_errors"] == 0
        assert snap["kernel_calls"] == 1
        assert snap["sustained_scenarios_per_sec"] > 0
        assert snap["latency"]["p95_ms"] >= snap["latency"]["p50_ms"]
        assert snap["queue_depth"] == 0

    def test_zero_scenarios_never_divides(self):
        # the empty grid is rejected before evaluation — no div-by-zero
        # path exists for scenarios_per_sec
        with SweepService(window_s=0.0) as svc:
            with pytest.raises(QueryError) as ei:
                svc.submit({"workloads": []})
            assert ei.value.code == "bad-query"

    def test_dispatcher_survives_a_failing_batch(self, monkeypatch):
        """An exception outside a group's evaluation (here the jax
        structure probe) fails that batch's tickets with a structured
        error, and the dispatcher goes on to answer the next query."""
        import repro.core.batched_jax as bj

        def broken_probe(grid):
            raise RuntimeError("structure probe exploded")

        monkeypatch.setattr(bj, "jax_evaluator_cached", broken_probe)
        spec = {"workloads": ["resnet50"], "workers": [4], "seed": 7}
        with SweepService(window_s=0.0) as svc:
            with pytest.raises(QueryError) as ei:
                svc.query({**spec, "backend": "jax"}, timeout=60)
            assert ei.value.code == "evaluation-failed"
            assert "structure probe exploded" in str(ei.value)
            res = svc.query(dict(spec), timeout=60)
            snap = svc.stats_snapshot()
        assert_tables_equal(res.table, reference(spec).columns)
        assert snap["n_errors"] == 1 and snap["n_queries"] == 1

    def test_close_resolves_pending(self):
        svc = SweepService(window_s=0.0)
        svc.close()
        with pytest.raises(RuntimeError):
            svc.submit({"workloads": ["resnet50"], "workers": [4]})


# ----------------------------------------------------------------------
# HTTP launcher
# ----------------------------------------------------------------------
@pytest.fixture(scope="class")
def server():
    from repro.launch.serve_sweep import make_server

    srv = make_server(port=0, window_s=0.02)
    thread = threading.Thread(target=srv.serve_forever, daemon=True)
    thread.start()
    yield srv
    srv.shutdown()
    srv.server_close()
    srv.service.close()


def http_query(srv, doc: dict) -> list[dict]:
    port = srv.server_address[1]
    req = urllib.request.Request(f"http://127.0.0.1:{port}/query",
                                 data=json.dumps(doc).encode(),
                                 method="POST")
    with urllib.request.urlopen(req) as resp:
        return [json.loads(line) for line in resp]


class TestHTTPServer:
    def test_round_trip_bit_identity(self, server):
        from repro.launch.serve_sweep import table_from_wire

        spec = {"workloads": ["resnet50"], "workers": [4, 8], "seed": 7}
        lines = http_query(server, spec)
        assert lines[0]["type"] == "header"
        assert lines[0]["columns"] == list(COLUMNS)
        assert lines[0]["format"] == "columns"
        assert lines[-1]["type"] == "trailer"
        assert_tables_equal(table_from_wire(lines),
                            reference(spec).columns)

    def test_rows_format_round_trip(self, server):
        from repro.launch.serve_sweep import table_from_wire

        spec = {"workloads": ["resnet50"], "workers": [4], "seed": 7,
                "format": "rows"}
        lines = http_query(server, spec)
        assert lines[0]["format"] == "rows"
        rows = [r for ln in lines if ln["type"] == "rows"
                for r in ln["rows"]]
        want = reference({k: v for k, v in spec.items()
                          if k != "format"}).columns
        assert_tables_equal(table_from_rows(rows), want)
        assert_tables_equal(table_from_wire(lines), want)

    def test_trailer_keys(self, server):
        lines = http_query(server, {"workloads": ["resnet50"],
                                    "workers": [4]})
        trailer = lines[-1]
        assert set(trailer) == {"type", "qos"} | set(RESULT_META_KEYS)
        assert set(trailer["qos"]) >= {"queue_wait_s", "latency_s",
                                       "coalesced_queries", "cache"}

    @pytest.mark.parametrize("body,code", [
        (b"{not json", "bad-json"),
        (json.dumps({"workloads": []}).encode(), "bad-query"),
        (json.dumps({"grid": "nope"}).encode(), "bad-query"),
        (json.dumps({"sync_k": ["-1"]}).encode(), "bad-query"),
        (json.dumps({"format": "xml"}).encode(), "bad-query"),
    ])
    def test_malformed_gets_structured_400(self, server, body, code):
        port = server.server_address[1]
        req = urllib.request.Request(f"http://127.0.0.1:{port}/query",
                                     data=body, method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        doc = json.loads(ei.value.read())
        assert doc["type"] == "error" and doc["code"] == code
        assert "Traceback" not in doc["error"]

    def test_unknown_endpoint_404(self, server):
        port = server.server_address[1]
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope")
        assert ei.value.code == 404

    def test_client_disconnect_mid_stream_keeps_serving(self, server):
        port = server.server_address[1]
        body = json.dumps({"grid": "frontier",
                           "workloads": ["resnet50"],
                           "workers": [8], "seed": 7}).encode()
        sock = socket.create_connection(("127.0.0.1", port))
        sock.sendall(b"POST /query HTTP/1.0\r\n"
                     b"Content-Length: %d\r\n\r\n%s"
                     % (len(body), body))
        sock.recv(512)          # read a little, then hang up
        sock.close()
        # the server must still answer the next query, bit-identically
        from repro.launch.serve_sweep import table_from_wire

        spec = {"workloads": ["resnet50"], "workers": [4], "seed": 7}
        lines = http_query(server, spec)
        assert_tables_equal(table_from_wire(lines),
                            reference(spec).columns)

    def test_concurrent_clients_bit_identity(self, server):
        from repro.launch.serve_sweep import table_from_wire

        refs = [reference(s).columns for s in COALESCE_SPECS]
        out = [None] * len(COALESCE_SPECS)

        def run(i, spec):
            out[i] = table_from_wire(http_query(server, spec))

        threads = [threading.Thread(target=run, args=(i, s))
                   for i, s in enumerate(COALESCE_SPECS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for got, ref in zip(out, refs):
            assert_tables_equal(got, ref)

    def test_stats_and_healthz(self, server):
        port = server.server_address[1]
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/healthz") as r:
            assert json.loads(r.read()) == {"ok": True}
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/stats") as r:
            stats = json.loads(r.read())
        for key in ("n_queries", "kernel_calls", "coalesce_factor",
                    "latency", "queue_wait", "cache", "queue_depth",
                    "sustained_scenarios_per_sec", "uptime_s"):
            assert key in stats, key


# ----------------------------------------------------------------------
# satellites: spec parity + warmed pools
# ----------------------------------------------------------------------
class TestGridSpecParity:
    def test_grid_from_spec_matches_cli_parsing(self):
        from repro.launch.sweep import build_parser, grid_from_args

        parser = build_parser()
        args = parser.parse_args(
            ["--grid", "mixed", "--workloads", "resnet50,alexnet",
             "--workers", "4,8", "--sync-k", "none,3"])
        from_cli = grid_from_args(args)
        from_spec = grid_from_spec(
            {"grid": "mixed", "workloads": "resnet50,alexnet",
             "workers": "4,8", "sync_k": "none,3"})
        assert from_cli == from_spec


class TestWarmPool:
    def test_warm_pool_then_parallel_sweep_bit_identical(self):
        from repro.core import parallel
        from repro.core.scenarios import default_grid

        parallel.warm_pool("process", jobs=2)
        grid = default_grid()
        ref = sweep(grid, seed=3)
        par = sweep(grid, jobs=2, seed=3)
        assert_tables_equal(par.columns, ref.columns)

    def test_warm_pool_serial_noop(self):
        from repro.core import parallel
        parallel.warm_pool("process", jobs=1)   # must not build a pool
