"""Tests of the chip benchmark's harness, on the CPU.

They load no TPU library and start no child process: every run here
goes through :func:`chipbench.run.run_cell` in this process with the
look for a chip skipped (``require_tpu=False``), at a size the CPU
holds, and with the persistent compile cache left off.
"""
from __future__ import annotations

import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from chipbench import check, faults, load, run, trace  # noqa: E402

#: Two 2 160-scenario sweeps profiled on one TPU v5e, each under a
#: ``chipbench.request`` annotation, 10 ms apart (gzip of the .xplane.pb).
FIXTURE = (Path(__file__).resolve().parent / "fixtures"
           / "kernel_trace.xplane.pb.gz")


def _bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


# ----------------------------------------------------------------------
# A small cell, added from a temporary directory with new files and new
# BENCHMARK.json entries only.
# ----------------------------------------------------------------------
TINY_GRID = {
    "workloads": ["alexnet", "resnet50"], "clusters": ["v100-nvlink-ib"],
    "worker_counts": [2, 8], "policies": ["tensorflow", "bucketed-4mb"],
    "collectives": ["ring", "hierarchical"], "link_bases": ["ib-100g"],
    "bw_factors": [0.5, 1, 2, 4], "lat_factors": [0.25, 1, 4],
    "het_profiles": [None],
    "stragglers": [None]}

ROWS_READER = '''"""Sampled rows per request (a metric a later change could add)."""


def read(run):
    done = [r for r in run.records if r.error is None]
    return sum(len(r.rows) for r in done) / len(done) if done else None
'''


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A checkout-shaped directory holding the real benchmark plus a new
    cell, a new configuration with its own reference (a copy of
    frontier's), a new traffic mix and a new per-layer metric."""
    for sub in ("configs", "references", "traffic", "drivers", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, tmp_path / "chipbench" / sub)
    shutil.copy(ROOT / "chipbench/references/frontier.py",
                tmp_path / "chipbench/references/tiny.py")
    frontier = json.loads(
        (ROOT / "chipbench/configs/frontier.json").read_text())
    axis_order = frontier["grid"]["axis_order"]
    cfg = {**frontier, "name": "tiny",
           "grid": {"axis_order": axis_order, **TINY_GRID}}
    (tmp_path / "chipbench/configs/tiny.json").write_text(json.dumps(cfg))
    sweeps = json.loads(
        (ROOT / "chipbench/traffic/fresh_frontier.json").read_text())
    sweeps["warmup_requests"] = 1
    (tmp_path / "chipbench/traffic/tiny_sweeps.json").write_text(
        json.dumps(sweeps))
    (tmp_path / "chipbench/metrics/rows_checked.sweep.py").write_text(
        ROWS_READER)
    bench = _bench()
    bench["configs"] += [{"name": "tiny", "source": "test", "reduced": [],
                          "file": "chipbench/configs/tiny.json", "why": "t"}]
    bench["workloads"] += [
        {"name": "tiny.sweep", "config": "tiny", "traffic": "tiny_sweeps",
         "chips": 1, "why": "t"}]
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"] += ["tiny.sweep"]
    bench["per_layer"].append(
        {"name": "rows_checked.sweep", "unit": "rows", "better": "higher",
         "source": "host_clock", "layer": "benchmark", "moves":
         "scenarios_per_s", "workloads": ["tiny.sweep"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compilation_cache", lambda: "off")
    return tmp_path


def _run(root, cell, seed=2_147_483_659, seconds=0.5, traced=False,
         control=False):
    return run.run_cell(cell, seed, seconds, traced, root=root,
                        require_tpu=False, control=control)


def _add_cell(root, cell: dict) -> None:
    """Append ``cell`` to ``<root>/BENCHMARK.json``, reporting every
    end-to-end metric that names its cells."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append(cell)
    for m in bench["end_to_end"]:
        if "workloads" in m:
            m["workloads"].append(cell["name"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


#: A reference that puts every iteration time one part in 10**6 high.
PERTURBED = '''

_row = Reference.row


def _perturbed(self, s):
    row = _row(self, s)
    return {**row, "iteration_time_s": row["iteration_time_s"] * (1 + 1e-6)}


Reference.row = _perturbed
'''

#: The driver of a new traffic mode: the sweeps of mode ``sweep``, a
#: fixed number of them however long they take.
COUNTED_DRIVER = '''"""Traffic mode ``counted``: ``requests`` closed-loop sweeps."""
import time

from chipbench.drivers.sweep import Driver as Sweeps, request
from chipbench.load import WINDOW, Record, _attempt


class Driver(Sweeps):
    def window(self, seconds, annotate=None):
        records, t_start = [], time.perf_counter()
        for index in range(self.traffic["requests"]):
            req = request(self.config, self.traffic, self.seed, WINDOW, index)
            rec = Record(req, t0=time.perf_counter())
            _attempt(self, index, rec, annotate)
            records.append(rec)
        return records, records[-1].t1 - t_start
'''


# ----------------------------------------------------------------------
# Discovery by name.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    spec = run.resolve(ROOT, cell)
    assert spec["config"]["name"] == spec["cell"]["config"]
    assert Path(spec["reference"].__file__) == (
        ROOT / "chipbench/references" / f"{spec['cell']['config']}.py")
    for name in ("Reference", "scenario_at", "LABEL_COLUMNS",
                 "NUMERIC_COLUMNS"):
        assert hasattr(spec["reference"], name)
    assert Path(spec["driver"].__file__) == (
        ROOT / "chipbench/drivers" / f"{spec['traffic']['mode']}.py")
    assert callable(spec["driver"].Driver)
    assert callable(spec["driver"].request)
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    assert "setup_s" in names
    for name in names:
        assert callable(run.reader(ROOT, name).read)


def test_cell_added_from_a_temporary_directory_runs(tiny_root):
    spec = run.resolve(tiny_root, "tiny.sweep")
    assert spec["config"]["grid"]["workloads"] == ["alexnet", "resnet50"]
    assert [m["name"] for m in spec["per_layer"]][-1] == "rows_checked.sweep"
    res = _run(tiny_root, "tiny.sweep", traced=True)
    assert res["correct"], res["checks"]
    assert res["metrics"]["rows_checked.sweep"]["value"] == 4
    assert res["checks"]["compiles_in_window"]["value"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("seed", [2_147_483_659, 3_000_000_019])
def test_small_cells_are_correct_end_to_end(tiny_root, seed):
    res = _run(tiny_root, "tiny.sweep", seed=seed)
    metric = "scenarios_per_s"
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["metrics"][metric]["value"] > 0
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["checks"]["max_rel_err"]["value"] <= 1e-13


# ----------------------------------------------------------------------
# A configuration's own reference, a mode's own driver, the grid's axes.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("perturbed", [False, True])
def test_configuration_brings_its_own_reference(tiny_root, perturbed):
    path = tiny_root / "chipbench/references/tiny.py"
    if perturbed:
        path.write_text(path.read_text() + PERTURBED)
    assert Path(run.resolve(tiny_root, "tiny.sweep")["reference"].__file__) \
        == path
    res = _run(tiny_root, "tiny.sweep")
    err = res["checks"]["max_rel_err"]
    assert res["checks"]["label_mismatches"]["value"] == 0
    if perturbed:
        assert not res["correct"]
        assert 0.5e-6 < err["value"] < 2e-6 and err["value"] > err["limit"]
    else:
        assert res["correct"], res["checks"]
        assert err["value"] <= 1e-13


def test_missing_reference_fails_in_resolve_and_names_the_file(tiny_root):
    path = tiny_root / "chipbench/references/tiny.py"
    path.unlink()
    with pytest.raises(FileNotFoundError, match=re.escape(str(path))):
        run.resolve(tiny_root, "tiny.sweep")


def test_driver_of_a_new_mode_is_found_and_run(tiny_root):
    (tiny_root / "chipbench/drivers/counted.py").write_text(COUNTED_DRIVER)
    traffic = json.loads(
        (tiny_root / "chipbench/traffic/tiny_sweeps.json").read_text())
    traffic.update(mode="counted", requests=3)
    (tiny_root / "chipbench/traffic/tiny_counted.json").write_text(
        json.dumps(traffic))
    _add_cell(tiny_root, {"name": "tiny.counted", "config": "tiny",
                          "traffic": "tiny_counted", "chips": 1, "why": "t"})
    driver = run.resolve(tiny_root, "tiny.counted")["driver"]
    assert Path(driver.__file__) == tiny_root / "chipbench/drivers/counted.py"
    res = _run(tiny_root, "tiny.counted", seconds=60.0)
    assert res["correct"], res["checks"]
    assert res["attempted"] == 3 and res["failed"] == 0
    assert res["checks"]["rows_checked"]["value"] == 3 * 4
    assert res["metrics"]["scenarios_per_s"]["value"] > 0


def test_scenario_grid_passes_every_named_axis():
    from repro.core.scenarios import ScenarioGrid

    axes = {"axis_order": ["workloads", "worker_counts", "sync_ks",
                           "faults"],
            "workloads": ["resnet50"], "worker_counts": [4, 8],
            "sync_ks": [None, 2], "faults": [None, "fail:0.01@restart2.5"]}
    grid = load.scenario_grid(axes)
    assert grid == ScenarioGrid(workloads=("resnet50",), worker_counts=(4, 8),
                                sync_ks=(None, 2),
                                faults=(None, "fail:0.01@restart2.5"))
    assert len(grid) == 2 * 2 * 2 * len(ScenarioGrid().clusters) \
        * len(ScenarioGrid().policies)


@pytest.mark.parametrize("axis", ["ep_degrees", "batch_per_gpu"])
def test_scenario_grid_refuses_a_name_that_is_no_axis(axis):
    axes = {"axis_order": ["workloads", axis], "workloads": ["resnet50"],
            axis: [2]}
    with pytest.raises(ValueError, match=axis):
        load.scenario_grid(axes)


# ----------------------------------------------------------------------
# The check rejects the control and every planted fault.
# ----------------------------------------------------------------------
def test_float32_control_is_not_correct(tiny_root):
    res = _run(tiny_root, "tiny.sweep", control=True)
    assert not res["correct"]
    err = res["checks"]["max_rel_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(tiny_root, fault):
    undo = faults.plant(fault)
    try:
        res = _run(tiny_root, "tiny.sweep", seconds=1.0)
    finally:
        undo()
    assert not res["correct"], res["checks"]


def test_refuses_to_run_without_a_tpu(capsys):
    rc = run.main(["--workload", "frontier.sweep", "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0
    assert out == ""
    assert "no TPU" in err and "cpu" in err


# ----------------------------------------------------------------------
# Traffic.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cell", [w["name"] for w in _bench()["workloads"]])
def test_traffic_is_fixed_by_the_seed(cell):
    spec = run.resolve(ROOT, cell)
    config, traffic = spec["config"], spec["traffic"]
    request = spec["driver"].request

    def mix(seed):
        reqs = [request(config, traffic, seed, load.WINDOW, i)
                for i in range(40)]
        rows = [load.sample_rows(r.size, 4, seed, i).tolist()
                for i, r in enumerate(reqs)]
        return [r.axes for r in reqs], rows

    big = 3_000_000_019
    assert mix(big) == mix(big)
    assert mix(big) != mix(big + 1)
    sizes = {request(config, traffic, s, load.WINDOW, i).size
             for s in (1, big) for i in range(40)}
    assert len(sizes) == 1            # every seed: the same sizes


def test_fresh_factors_are_distinct_and_in_range():
    traffic = run.load_json(ROOT / "chipbench/traffic/fresh_frontier.json")
    spec = traffic["link_factors"]
    for i in range(200):
        rng = np.random.default_rng([5, 0, i])
        for name in ("bw_factors", "lat_factors"):
            vals = load.draw_factors(rng, spec[name], spec["digits"])
            assert len(set(vals)) == spec[name]["count"]
            assert all(spec[name]["low"] * 0.99 <= v <= spec[name]["high"]
                       * 1.01 for v in vals)


def test_frontier_config_is_frontier_grid_at_published_factors():
    from repro.core.scenarios import frontier_grid

    config = run.load_json(ROOT / "chipbench/configs/frontier.json")
    grid = load.scenario_grid(load.concrete_axes(config["grid"]))
    assert grid == frontier_grid()
    assert len(grid) == 51_840


# ----------------------------------------------------------------------
# The reference.
# ----------------------------------------------------------------------
def _frontier_reference():
    return run.code(ROOT, "references", "frontier")


def test_reference_matches_the_numpy_engine_on_the_frontier():
    from repro.core.sweep import sweep

    reference = _frontier_reference()
    config = run.load_json(ROOT / "chipbench/configs/frontier.json")
    axes = load.concrete_axes(config["grid"])
    res = sweep(load.scenario_grid(axes), backend="numpy")
    ref = reference.Reference(config["model"])
    for i in np.random.default_rng(0).choice(len(res), 120, replace=False):
        want = ref.row(reference.scenario_at(axes, int(i)))
        for c in reference.LABEL_COLUMNS:
            assert res.columns[c][i] == want[c]
        for c in reference.NUMERIC_COLUMNS:
            assert res.columns[c][i] == pytest.approx(want[c], rel=1e-13)


@pytest.mark.parametrize("het,straggler", [("het:1x0.5+3x1.0", None),
                                           (None, "lognormal:0.2")])
def test_reference_refuses_what_it_does_not_model(het, straggler):
    reference = _frontier_reference()
    config = run.load_json(ROOT / "chipbench/configs/frontier.json")
    axes = {**load.concrete_axes(config["grid"]), "het_profiles": [het],
            "stragglers": [straggler]}
    with pytest.raises(ValueError, match="not modelled"):
        reference.Reference(config["model"]).row(
            reference.scenario_at(axes, 0))


def test_check_counts_label_mismatches_and_failures():
    spec = run.resolve(ROOT, "frontier.sweep")
    config, reference = spec["config"], spec["reference"]
    req = spec["driver"].request(config, {"mode": "sweep"}, 1, load.WINDOW, 0)
    ref = reference.Reference(config["model"])
    good = ref.row(reference.scenario_at(req.axes, 5))
    rec = load.Record(req, t0=0.0, t1=1.0, rows={5: dict(good),
                                                 6: dict(good)})
    failed = load.Record(req, t0=0.0, t1=1.0, error="boom")
    got = check.compare(reference, config, [rec, failed])
    assert got["label_mismatches"]["value"] == 1      # row 6 is not row 5
    assert got["failed_requests"]["value"] == 1
    assert got["rows_checked"]["value"] == 2
    assert not check.passed(got)


# ----------------------------------------------------------------------
# Timers and the trace reduction.
# ----------------------------------------------------------------------
def test_timer_of_a_missing_function_is_reported_missing():
    timers = trace.HostTimers()
    timers.install("repro.core.batched_jax:JaxGridEvaluator.no_such_method")
    timers.install("repro.no_such_module:f")
    assert len(timers.missing) == 2 and timers.spans == {}
    view = run.RunView(records=[], window_s=1.0, setup_s=1.0, timers=timers)
    for name in ("columns_ms.sweep", "frontend_ms.sweep", "kernel_ms.sweep",
                 "device_idle_pct.sweep"):
        assert run.reader(ROOT, name).read(view) is None


class _Ev:
    def __init__(self, name, start_ns, duration_ns):
        self.name, self.start_ns, self.duration_ns = name, start_ns, duration_ns


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


def test_reduction_takes_the_union_of_overlapping_ops():
    ops = [_Ev("a", 0, 10), _Ev("b", 5, 10), _Ev("c", 30, 5)]
    mods = [_Ev("jit__columns_jax(7)", 0, 15), _Ev("jit__columns_jax(7)",
                                                    30, 5)]
    host = [_Ev("chipbench.request", 0, 40)]
    red = trace.reduce_planes([
        _Plane("/device:TPU:0", [_Line("XLA Ops", ops),
                                 _Line("XLA Modules", mods)]),
        _Plane("/host:CPU", [_Line("python", host)])],
        ["chipbench.request"])
    dev = red["devices"]["/device:TPU:0"]
    assert dev["busy_s"] == pytest.approx(20e-9)
    assert dev["modules"] == {"jit__columns_jax": pytest.approx(20e-9)}
    assert dev["gaps"] == [pytest.approx((15e-9, 30e-9))]
    bd = trace.breakdown(red)
    assert bd["device_ops"][0][0] in ("a", "b")
    assert bd["idle_gaps"] == [["chipbench.request", pytest.approx(15e-9)]]


def test_reduction_of_a_trace_recorded_on_the_chip(tmp_path):
    import gzip

    path = tmp_path / "kernel_trace.xplane.pb"
    path.write_bytes(gzip.decompress(FIXTURE.read_bytes()))
    red = trace.reduce_xspace(str(path), ["chipbench.request"])
    dev_name = trace.fullest(red)
    assert dev_name is not None and dev_name.startswith("/device:TPU:")
    dev = red["devices"][dev_name]
    span = dev["last_s"] - dev["first_s"]
    assert 0 < dev["busy_s"] <= span
    assert dev["modules"]["jit__columns_jax"] > 0
    assert dev["modules"]["jit__columns_jax"] <= span
    spans = [s for s in red["host_spans"] if s[0] == "chipbench.request"]
    assert len(spans) == 2
    # the device and the host share the trace's clock: every kernel op
    # falls inside a request
    assert spans[0][1] <= dev["first_s"] and dev["last_s"] <= spans[1][2]
    bd = trace.breakdown(red)
    assert all(not name.count(" = ") for name, _ in bd["device_ops"])
    assert bd["idle_gaps"][0][0] == "between requests"
