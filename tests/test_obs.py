"""The span and counter recorder (repro.core.obs) and its spans on the
jax grid path."""
from __future__ import annotations

import threading

import jax
import numpy as np
import pytest

from repro.core import batched_jax as BJ
from repro.core import obs
from repro.core.scenarios import ScenarioGrid
from repro.core.sweep import iter_tables, sweep

SWEEP_SPANS = ("sweep", "sweep.build", "sweep.columns", "sweep.columns.call",
               "sweep.columns.wait", "sweep.columns.fetch", "sweep.labels")


def _grid(workers=(2, 8)) -> ScenarioGrid:
    return ScenarioGrid(workloads=("alexnet", "resnet50"),
                        clusters=("v100-nvlink-ib",), worker_counts=workers,
                        policies=("tensorflow", "bucketed-4mb", "caffe-mpi"),
                        collectives=("ring", "hierarchical"))


@pytest.fixture
def recorder():
    obs.snapshot()
    obs.enable()
    try:
        yield obs
    finally:
        obs.disable()
        obs.snapshot()


def _by_name(spans) -> dict:
    out: dict = {}
    for s in spans:
        out.setdefault(s.name, []).append(s)
    return out


def _inside(child, parent) -> bool:
    return parent.t0 <= child.t0 and child.t1 <= parent.t1


# ----------------------------------------------------------------------
# The recorder itself.
# ----------------------------------------------------------------------
def test_off_records_nothing_and_enters_no_annotation(monkeypatch):
    class Refused:
        def __init__(self, *a, **k):
            raise AssertionError("a trace annotation was entered while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Refused)
    monkeypatch.setattr(obs, "_annotation", Refused)
    obs.disable()
    obs.snapshot()
    assert obs.span("sweep") is obs.span("sweep.columns")
    with obs.span("sweep"):
        obs.count("sweep.builds")
    sweep(_grid((2, 4)), backend="jax")
    assert obs.snapshot() == {"spans": [], "counters": {}}


def test_nested_spans_name_their_parent_and_share_one_sweep_id(recorder):
    with obs.span("sweep"):
        with obs.span("sweep.columns"):
            with obs.span("sweep.columns.call"):
                pass
    with obs.span("sweep"):
        pass
    with obs.span("elsewhere"):
        pass
    spans = obs.snapshot()["spans"]
    by = _by_name(spans)
    call, = by["sweep.columns.call"]
    cols, = by["sweep.columns"]
    first, second = sorted(by["sweep"], key=lambda s: s.t0)
    assert (call.parent, cols.parent, first.parent) == (
        "sweep.columns", "sweep", None)
    assert call.sweep_id == cols.sweep_id == first.sweep_id is not None
    assert second.sweep_id != first.sweep_id
    assert by["elsewhere"][0].sweep_id is None
    assert _inside(call, cols) and _inside(cols, first)


def test_threads_keep_separate_stacks(recorder):
    entered, release = threading.Barrier(2), threading.Event()

    def work(name):
        with obs.span(name):
            entered.wait(timeout=10)
            release.wait(timeout=10)
            with obs.span(name + ".child"):
                pass

    threads = [threading.Thread(target=work, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    release.set()
    for t in threads:
        t.join(timeout=10)
        assert not t.is_alive()
    parents = {s.name: s.parent for s in obs.snapshot()["spans"]}
    assert parents == {"a": None, "b": None, "a.child": "a", "b.child": "b"}


def test_counters_sum_and_snapshot_drains(recorder):
    obs.count("x")
    obs.count("x", 4)
    obs.count("y", 0)
    with obs.span("s"):
        pass
    got = obs.snapshot()
    assert got["counters"] == {"x": 5, "y": 0}
    assert [s.name for s in got["spans"]] == ["s"]
    assert obs.snapshot() == {"spans": [], "counters": {}}


# ----------------------------------------------------------------------
# Spans and counters on the jax grid path.
# ----------------------------------------------------------------------
def test_a_sweep_records_each_span_once_inside_its_parent(recorder):
    sweep(_grid((2, 16)), backend="jax")
    by = _by_name(obs.snapshot()["spans"])
    assert {k: len(v) for k, v in by.items()} == {k: 1 for k in SWEEP_SPANS}
    parent = {"sweep.build": "sweep", "sweep.columns": "sweep",
              "sweep.columns.call": "sweep.columns",
              "sweep.columns.wait": "sweep.columns",
              "sweep.columns.fetch": "sweep.columns",
              "sweep.labels": "sweep.columns"}
    root = by["sweep"][0]
    for name, outer in parent.items():
        span = by[name][0]
        assert span.parent == outer and span.sweep_id == root.sweep_id
        assert _inside(span, by[outer][0])
    call, wait, fetch = (by[f"sweep.columns.{p}"][0]
                         for p in ("call", "wait", "fetch"))
    labels, = by["sweep.labels"]
    assert call.t1 <= labels.t0 and labels.t1 <= wait.t0
    assert wait.t1 <= fetch.t0
    assert by["sweep.build"][0].t1 <= by["sweep.columns"][0].t0


def test_a_second_sweep_of_the_same_grid_builds_nothing(recorder):
    grid = _grid((2, 32))
    sweep(grid, backend="jax")
    first = obs.snapshot()
    sweep(grid, backend="jax")
    second = obs.snapshot()
    assert first["counters"]["sweep.builds"] == 1
    assert second["counters"]["sweep.builds"] == 0
    assert "sweep.build" not in {s.name for s in second["spans"]}


def test_h2d_counters_are_the_numpy_leaves_of_the_call(recorder):
    """What crosses to the device is two packed host buffers, float64
    and int32, holding every NumPy leaf of the call."""
    grid = _grid((4, 8))
    sweep(grid, backend="jax")
    counters = obs.snapshot()["counters"]
    args = BJ.jax_grid_evaluator(grid)._args()
    host = [x for x in jax.tree_util.tree_leaves(args)
            if isinstance(x, np.ndarray)]
    f64, i32, live, *_ = BJ._packed_args(args)
    assert live == () and len(host) > 2
    assert f64.size + i32.size == sum(x.size for x in host)
    assert counters["sweep.h2d_arrays"] == 2
    assert counters["sweep.h2d_bytes"] == f64.nbytes + i32.nbytes
    assert counters["sweep.h2d_bytes"] == 8 * f64.size + 4 * i32.size


def test_d2h_counters_are_one_packed_buffer(recorder):
    grid = _grid((4, 16))
    sweep(grid, backend="jax")
    counters = obs.snapshot()["counters"]
    S = len(BJ.jax_grid_evaluator(grid))
    assert counters["sweep.d2h_arrays"] == 1
    assert counters["sweep.d2h_bytes"] == len(BJ._NUMERIC_COLS) * S * 8


def test_traced_columns_stay_a_dict_of_the_six_columns():
    jev = BJ.jax_grid_evaluator(_grid((2, 4)))
    with jax.enable_x64(True):
        traced = jev._traced_columns()
        assert tuple(traced) == BJ._NUMERIC_COLS
        host = {k: np.asarray(v) for k, v in traced.items()}
    fetched = jev.columns()
    for k, v in host.items():
        assert v.shape == (len(jev),) and v.dtype == np.float64
        assert np.array_equal(v.view(np.uint64),
                              fetched[k].view(np.uint64)), k


def test_grad_iteration_time_equals_the_gradient_through_the_packed_kernel():
    grid = _grid((4, 8))
    jev = BJ.jax_grid_evaluator(grid)
    S = len(jev)
    got = BJ.grad_iteration_time(grid)
    row = BJ._NUMERIC_COLS.index("iteration_time_s")
    with jax.enable_x64(True):
        p = {k: jax.numpy.asarray(v)
             for k, v in BJ.default_params(grid).items()}
        def total(q):           # the params pass through as live leaves
            return BJ._columns_jax(*BJ._packed_args(jev._args(q)))[
                row, :S].sum()

        want = jax.grad(total)(p)
    assert got.keys() == want.keys()
    assert any(np.abs(got[k]).max() > 0 for k in ("intra_bw", "inter_bw"))
    for k in got:
        assert np.array_equal(got[k], np.asarray(want[k])), k


def test_columns_are_bit_identical_with_the_recorder_on_and_off():
    grid = _grid((2, 64))
    obs.disable()
    off = sweep(grid, backend="jax").columns
    obs.enable()
    try:
        on = sweep(grid, backend="jax").columns
    finally:
        obs.disable()
        obs.snapshot()
    assert off.keys() == on.keys()
    for k in off:
        assert np.array_equal(off[k], on[k]), k


def test_the_scenario_list_path_records_the_same_device_spans(recorder):
    scenarios = list(_grid((2, 8)))
    table = BJ.eval_scenarios_table_jax(scenarios)
    got = obs.snapshot()
    names = [s.name for s in got["spans"]]
    for name in SWEEP_SPANS[2:]:        # a list has no grid labels to gather
        assert names.count(name) == (name != "sweep.labels")
    assert got["counters"]["sweep.h2d_arrays"] > 0
    assert "sweep.labels_overlapped" not in got["counters"]
    obs.disable()
    plain = BJ.eval_scenarios_table_jax(scenarios)
    for k in table:
        assert np.array_equal(np.asarray(table[k]), np.asarray(plain[k])), k


# ----------------------------------------------------------------------
# The labels of a grid sweep are gathered while its kernel runs.
# ----------------------------------------------------------------------
MC_GRID = ScenarioGrid(workloads=("resnet50",), clusters=("v100-nvlink-ib",),
                       worker_counts=(4, 16),
                       policies=("tensorflow", "bucketed-4mb"),
                       stragglers=("none", "lognormal:0.2x100"))


@pytest.mark.parametrize("route", ["whole", "chunked", "monte_carlo"])
def test_labels_are_gathered_between_the_call_and_the_wait(recorder, route):
    grid = MC_GRID if route == "monte_carlo" else _grid((2, 8))
    chunk = 7 if route == "chunked" else None
    for _ in range(3):
        if chunk:
            tables = list(iter_tables(grid, backend="jax", chunk=chunk))
            assert len(tables) > 1
        else:
            sweep(grid, backend="jax", seed=4)
    got = obs.snapshot()
    by = _by_name(got["spans"])
    labels = sorted(by["sweep.labels"], key=lambda s: s.t0)
    assert len(labels) == 3
    assert got["counters"]["sweep.labels_overlapped"] == 3
    for span, cols, call, wait, fetch in zip(labels, *(
            sorted(by[n], key=lambda s: s.t0) for n in SWEEP_SPANS[2:6])):
        assert span.parent == "sweep.columns" and _inside(span, cols)
        assert call.t1 <= span.t0 and span.t1 <= wait.t0
        assert wait.t1 <= fetch.t0


def test_with_the_recorder_off_the_labels_come_before_the_fetch(monkeypatch):
    """The untimed path does the gathers while the kernel is in flight
    too: after the launch, before the blocking fetch of its output."""
    order = []
    gather, unpack, launch = BJ._slice_labels, BJ._unpack, BJ._columns_jax

    def logged(name, f):
        def g(*a, **k):
            order.append(name)
            return f(*a, **k)
        return g

    monkeypatch.setattr(BJ, "_slice_labels", logged("labels", gather))
    monkeypatch.setattr(BJ, "_unpack", logged("fetch", unpack))
    monkeypatch.setattr(BJ, "_columns_jax", logged("launch", launch))
    obs.disable()
    grid = _grid((2, 4))
    sweep(grid, backend="jax")
    assert order == ["launch", "labels", "fetch"]
    order.clear()
    list(iter_tables(grid, backend="jax", chunk=5))
    assert order[:3] == ["launch", "labels", "fetch"]
    assert order[3:] == ["labels"] * (-(-len(grid) // 5) - 1)


# ----------------------------------------------------------------------
# Expert parallelism: its span and counters fire only where some point
# has ep > 1.
# ----------------------------------------------------------------------
def _moe_grid(ep_sizes) -> ScenarioGrid:
    return ScenarioGrid(workloads=("llm:qwen2-moe-a2.7b",),
                        clusters=("v100-nvlink-ib",), worker_counts=(4, 8),
                        ep_sizes=ep_sizes,
                        policies=("tensorflow", "bucketed-25mb"),
                        collectives=("ring", "hierarchical"))


@pytest.mark.parametrize("ep_sizes", [(1,), (1, 2, 4)])
def test_ep_span_and_counters_fire_only_with_ep_above_one(recorder,
                                                          ep_sizes):
    grid = _moe_grid(ep_sizes)
    sweep(grid, backend="jax")
    got = obs.snapshot()
    by, counters = _by_name(got["spans"]), got["counters"]
    jev = BJ.jax_grid_evaluator(grid)
    kernel_points = len(jev._kcodes["n"])
    assert counters["sweep.kernel_points"] == kernel_points
    if ep_sizes == (1,):
        assert "sweep.build.ep" not in by
        assert "sweep.ep_h2d_bytes" not in counters
        assert counters["sweep.ep_points"] == 0
        return
    [ep] = by["sweep.build.ep"]
    assert ep.parent == "sweep.build" and _inside(ep, by["sweep.build"][0])
    ep_host = [v for k, v in jev._tables.items() if k.startswith("ep_")] \
        + [jev._kcodes["we"], jev._kcodes["ep"]]
    assert counters["sweep.ep_h2d_bytes"] == sum(       # as packed
        x.size * (8 if x.dtype.kind == "f" else 4) for x in ep_host)
    assert 0 < counters["sweep.ep_h2d_bytes"] < counters["sweep.h2d_bytes"]
    assert counters["sweep.ep_points"] * 3 == kernel_points * 2


def test_ep_is_live_on_six_of_seven_kernel_points_of_the_cell(recorder):
    """The grid of the benchmark cell ``dsv2_lite_ep.sweep`` (80 640
    scenarios): EP sizes 1-64 over 64-512 workers, two clusters."""
    from repro.core.hardware import COLLECTIVE_ALGORITHMS
    from repro.core.scenarios import FRONTIER_POLICIES

    links = tuple(f"{base}@bw{bw:g}@lat{lat:g}"
                  for base in ("10gbe", "ib-100g", "ib-100g-fused", "ib-200g")
                  for bw in (0.5, 1, 2, 4) for lat in (0.25, 1, 4))
    grid = ScenarioGrid(workloads=("llm:deepseek-v2-lite",),
                        clusters=("v100-nvlink-ib", "tpu-v5e-pod"),
                        worker_counts=(64, 128, 256, 512),
                        ep_sizes=(1, 2, 4, 8, 16, 32, 64),
                        policies=FRONTIER_POLICIES,
                        collectives=COLLECTIVE_ALGORITHMS, interconnects=links)
    assert len(grid) == 80_640
    sweep(grid, backend="jax")
    counters = obs.snapshot()["counters"]
    assert counters["sweep.kernel_points"] == 8_064
    assert counters["sweep.ep_points"] * 7 == counters["sweep.kernel_points"] * 6
