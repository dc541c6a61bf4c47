"""Tests of the readers of the program's own spans and counters
(``chipbench/recorder.py``, ``chipbench/spanclock.py``), on the CPU."""
from __future__ import annotations

import gzip
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from chipbench import recorder, run, spanclock, trace  # noqa: E402
from chipbench.test_chipbench import (  # noqa: E402
    FIXTURE, _Ev, _Line, _Plane, _run)
from chipbench.test_chipbench import tiny_root  # noqa: E402,F401  (fixture)

from repro.core import obs  # noqa: E402
from repro.core.obs import SpanRecord  # noqa: E402

#: Two 51 840-scenario sweeps of ``frontier.sweep`` profiled on one TPU
#: v5e with the program's recorder on (gzip of the .xplane.pb, without
#: its ``/host:metadata`` plane of compiled-program metadata).
SPANS_FIXTURE = FIXTURE.parent / "sweep_spans.xplane.pb.gz"

READERS = ("call_ms.sweep", "wait_ms.sweep", "fetch_ms.sweep",
           "build_ms.sweep", "builds_per_sweep.sweep", "h2d_transfers.sweep",
           "h2d_mb.sweep", "idle_in_columns_pct.sweep",
           "idle_in_frontend_pct.sweep")


@pytest.fixture(autouse=True)
def fresh_recorder(monkeypatch):
    """Each test starts with the recorder off and not yet armed."""
    monkeypatch.setattr(recorder, "_recorder", {"obs": None, "taken": False})
    obs.disable()
    obs.snapshot()
    yield
    obs.disable()
    obs.snapshot()


def _unpacked(tmp_path, fixture: Path) -> str:
    path = tmp_path / fixture.name.removesuffix(".gz")
    path.write_bytes(gzip.decompress(fixture.read_bytes()))
    return str(path)


# ----------------------------------------------------------------------
# Idle time split by overlap.
# ----------------------------------------------------------------------
def _split_view():
    """One sweep on a device idle from 10 to 40 ms: ``sweep.columns``
    open 0-15 and 35-50, the front end between; the recorder's clock is
    5 s behind the trace's, and the request opens with the sweep."""
    ms = 1_000_000
    ops = [_Ev("k", 0, 10 * ms), _Ev("k", 40 * ms, 10 * ms)]
    spans = [("sweep", 0, 50 * ms), ("sweep.columns", 0, 15 * ms),
             ("sweep.columns", 35 * ms, 15 * ms)]
    red = trace.reduce_planes(
        [_Plane("/device:TPU:0", [_Line("XLA Ops", ops)]),
         _Plane("/host:CPU", [_Line("python", [
             _Ev(recorder.REQUEST, 0, 51 * ms),
             *(_Ev(n, s, d) for n, s, d in spans)])])],
        [recorder.REQUEST, "sweep", "sweep.columns"])
    program = {"spans": [SpanRecord(n, None if n == "sweep" else "sweep", 1,
                                    s * 1e-9 - 5.0, (s + d) * 1e-9 - 5.0)
                         for n, s, d in spans], "counters": {}}
    view = run.RunView(records=[], window_s=1.0, setup_s=1.0,
                       reduced={**red, "host_spans": [
                           h for h in red["host_spans"]
                           if h[0] == recorder.REQUEST]},
                       trace_window_s=0.1)
    view.program = program
    return red, view


def test_a_gap_across_two_spans_is_split_by_overlap():
    red, view = _split_view()
    gaps = red["devices"]["/device:TPU:0"]["gaps"]
    assert gaps == [pytest.approx((0.010, 0.040))]
    # the midpoint rule gives the whole gap to the span open at 25 ms
    assert trace.breakdown(red)["idle_gaps"] == [
        ["sweep", pytest.approx(0.030)]]
    columns = recorder.on_trace_clock(view, "sweep.columns")
    assert recorder.idle_overlap(gaps, columns) == pytest.approx(0.010)
    read = {name: run.reader(ROOT, name).read(view) for name in READERS}
    assert read["idle_in_columns_pct.sweep"] == pytest.approx(10.0)
    assert read["idle_in_frontend_pct.sweep"] == pytest.approx(20.0)


def test_overlapping_spans_count_their_union_once():
    gaps = [(0.0, 10.0), (20.0, 30.0)]
    spans = [(5.0, 25.0), (8.0, 12.0), (29.0, 40.0)]
    assert recorder.idle_overlap(gaps, spans) == pytest.approx(5 + 5 + 1)
    assert recorder.idle_overlap(gaps, []) == 0.0


def test_requests_and_sweeps_that_do_not_pair_give_nothing():
    _, view = _split_view()
    view.program = {**view.program,
                    "spans": view.program["spans"] + [
                        SpanRecord("sweep", None, 2, -4.0, -3.9)]}
    assert recorder.on_trace_clock(view, "sweep.columns") is None
    assert run.reader(ROOT, "idle_in_columns_pct.sweep").read(view) is None


# ----------------------------------------------------------------------
# The readers.
# ----------------------------------------------------------------------
def _bare_view():
    return run.RunView(records=[], window_s=1.0, setup_s=1.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_recorders_data_is_none(name):
    assert run.reader(ROOT, name).read(_bare_view()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_program_without_a_recorder_is_none(name, monkeypatch):
    import repro.core

    monkeypatch.delattr(repro.core, "obs")
    monkeypatch.setitem(sys.modules, "repro.core.obs", None)   # import fails
    module = run.reader(ROOT, name)
    assert list(module.TIMED) == []
    assert not obs.enabled()
    assert module.read(_bare_view()) is None


def test_readers_divide_by_the_sweeps_recorded():
    def rec(name, parent, t0, t1):
        return SpanRecord(name, parent, 1, t0, t1)

    view = _bare_view()
    view.program = {
        "spans": [rec("sweep", None, 0.0, 0.050),
                  rec("sweep.columns.call", "sweep.columns", 0.01, 0.02),
                  rec("sweep", None, 0.1, 0.14),
                  rec("sweep.columns.call", "sweep.columns", 0.11, 0.13)],
        "counters": {"sweep.builds": 1, "sweep.h2d_arrays": 124,
                     "sweep.h2d_bytes": 3_000_000}}
    read = {name: run.reader(ROOT, name).read(view) for name in READERS}
    assert read["call_ms.sweep"] == pytest.approx(15.0)
    assert read["builds_per_sweep.sweep"] == 0.5
    assert read["h2d_transfers.sweep"] == 62
    assert read["h2d_mb.sweep"] == pytest.approx(1.5)
    for name in ("wait_ms.sweep", "fetch_ms.sweep", "build_ms.sweep",
                 "idle_in_columns_pct.sweep", "idle_in_frontend_pct.sweep"):
        assert read[name] is None                # no such span, no trace


def test_a_traced_run_arms_the_recorder_and_reads_it(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in READERS + ("columns_ms.sweep",):
            m["workloads"].append("tiny.sweep")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _run(tiny_root, "tiny.sweep", traced=True)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["builds_per_sweep.sweep"] == 1.0  # every sweep a new frontier
    assert got["h2d_transfers.sweep"] == int(got["h2d_transfers.sweep"]) > 0
    assert got["h2d_mb.sweep"] > 0
    for name in ("call_ms.sweep", "wait_ms.sweep", "fetch_ms.sweep",
                 "build_ms.sweep"):
        assert got[name] > 0
    parts = got["call_ms.sweep"] + got["wait_ms.sweep"] + got["fetch_ms.sweep"]
    assert parts <= got["columns_ms.sweep"]
    # the CPU has no device plane: nothing to split
    assert "idle_in_columns_pct.sweep" not in got
    assert not obs.enabled()
    assert obs.snapshot() == {"spans": [], "counters": {}}


def test_an_untraced_run_leaves_the_recorder_off(tiny_root):
    res = _run(tiny_root, "tiny.sweep", traced=False)
    assert res["correct"]
    assert recorder._recorder == {"obs": None, "taken": False}
    assert not obs.enabled()


# ----------------------------------------------------------------------
# Traces recorded on the chip.
# ----------------------------------------------------------------------
def test_breakdown_of_the_first_fixture_is_unchanged(tmp_path):
    red = trace.reduce_xspace(_unpacked(tmp_path, FIXTURE),
                              ["chipbench.request"])
    bd = trace.breakdown(red)
    assert [n for n, _ in bd["idle_gaps"]] == ["between requests",
                                               "chipbench.request"]
    assert [s for _, s in bd["idle_gaps"]] == pytest.approx(
        [0.023893921, 4.7632e-05], rel=1e-6)
    assert len(bd["device_ops"]) == 10


def test_breakdown_reads_only_the_names_it_always_read(tmp_path):
    path = _unpacked(tmp_path, SPANS_FIXTURE)
    old = ["chipbench.request"]
    red = trace.reduce_xspace(path, old + ["sweep", "sweep.columns"])
    kept = {**red, "host_spans": [h for h in red["host_spans"]
                                  if h[0] in old]}
    assert trace.breakdown(kept) == trace.breakdown(
        trace.reduce_xspace(path, old))


def test_program_spans_keep_their_plain_names_in_the_trace(tmp_path):
    red = trace.reduce_xspace(_unpacked(tmp_path, SPANS_FIXTURE),
                              ["chipbench.request", "sweep", "sweep.columns",
                               "sweep.columns.call", "sweep.columns.wait",
                               "sweep.columns.fetch", "sweep.build"])
    names = [h[0] for h in red["host_spans"]]
    for name in ("chipbench.request", "sweep", "sweep.columns",
                 "sweep.columns.call", "sweep.columns.wait",
                 "sweep.columns.fetch", "sweep.build"):
        assert names.count(name) == 2, name


def test_every_kernel_lies_inside_its_columns_span_and_call_to_wait(tmp_path):
    import jax

    data = jax.profiler.ProfileData.from_file(
        _unpacked(tmp_path, SPANS_FIXTURE))
    placed = spanclock.kernel_placement(list(data.planes))
    assert len(placed) == 2
    for p in placed:
        assert p["columns_margin_us"] >= 0
        assert p["call_wait_margin_us"] >= 0


def test_kernel_placement_measures_how_far_a_kernel_sticks_out():
    us = 1000
    host = [_Ev(n, s * us, d * us) for n, s, d in (
        ("sweep.columns", 0, 100), ("sweep.columns.call", 0, 10),
        ("sweep.columns.wait", 10, 60), ("sweep.columns.fetch", 70, 30),
        ("sweep.columns", 200, 100), ("sweep.columns.call", 200, 10),
        ("sweep.columns.wait", 210, 20))]
    kernels = [_Ev("jit__columns_jax(3)", 20 * us, 40 * us),
               _Ev("jit__columns_jax(3)", 220 * us, 15 * us)]
    placed = spanclock.kernel_placement([
        _Plane("/device:TPU:0", [_Line("XLA Modules", kernels)]),
        _Plane("/host:CPU", [_Line("python", host)])])
    assert placed[0] == {"columns_margin_us": pytest.approx(20),
                         "call_wait_margin_us": pytest.approx(10)}
    assert placed[1] == {"columns_margin_us": pytest.approx(20),
                         "call_wait_margin_us": pytest.approx(-5)}
