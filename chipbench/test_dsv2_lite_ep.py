"""Tests of the cell ``dsv2_lite_ep.sweep`` on the CPU.

A copy of the configuration, cut to one cluster, two worker counts and
two link bases, runs through the harness (:func:`chipbench.run.run_cell`
with the look for a chip skipped) from a temporary checkout-shaped
directory.  Its sampled answers must agree with the configuration's own
reference, ``chipbench/references/dsv2_lite_ep.py``, within the check's
limit; the same reference computed in float32, and the answers with a
fault of :mod:`chipbench.faults` planted, must not.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from chipbench import faults, load, recorder, run  # noqa: E402
from repro.core import obs  # noqa: E402

CELL = "dsv2_lite_ep.sweep"
CONFIG = ROOT / "chipbench/configs/dsv2_lite_ep.json"


@pytest.fixture
def small_root(tmp_path, monkeypatch):
    """The benchmark as it is, with the cell's grid cut to the
    ``v100-nvlink-ib`` cluster (4 devices a node, so EP groups of 8 and
    more cross nodes), 64 and 128 workers and two link bases, one
    warm-up sweep, ``build_ms.sweep`` and ``h2d_mb.sweep`` read in the
    cell too, and the program's recorder not yet armed."""
    for sub in ("configs", "references", "traffic", "drivers", "metrics"):
        shutil.copytree(ROOT / "chipbench" / sub, tmp_path / "chipbench" / sub)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in ("build_ms.sweep", "h2d_mb.sweep"):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    cfg = json.loads(CONFIG.read_text())
    cfg["grid"].update(clusters=["v100-nvlink-ib"], worker_counts=[64, 128],
                       link_bases=["10gbe", "ib-100g"])
    (tmp_path / "chipbench/configs/dsv2_lite_ep.json").write_text(
        json.dumps(cfg))
    path = tmp_path / "chipbench/traffic/fresh_frontier.json"
    traffic = json.loads(path.read_text())
    traffic["warmup_requests"] = 1
    path.write_text(json.dumps(traffic))
    import repro.launch.compile_cache as cc

    monkeypatch.setattr(cc, "enable_compilation_cache", lambda: "off")
    monkeypatch.setattr(recorder, "_recorder", {"obs": None, "taken": False})
    yield tmp_path
    obs.disable()
    obs.snapshot()


def _run(root, traced=False, control=False):
    return run.run_cell(CELL, 2_147_483_659, 0.5, traced, root=root,
                        require_tpu=False, control=control)


def test_cut_cell_is_correct_and_reads_its_ep_metrics(small_root):
    res = _run(small_root, traced=True)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    assert res["checks"]["max_rel_err"]["limit"] == 1e-9
    assert res["checks"]["label_mismatches"]["value"] == 0
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert 0 < got["ep_build_ms.sweep"] < got["build_ms.sweep"]
    assert 0 < got["ep_h2d_mb.sweep"] < got["h2d_mb.sweep"]


def test_float32_control_is_not_correct(small_root):
    res = _run(small_root, control=True)
    assert not res["correct"]
    err = res["checks"]["max_rel_err"]
    assert err["value"] > err["limit"]


@pytest.mark.parametrize("fault", faults.FAULTS)
def test_planted_fault_is_not_correct(small_root, fault):
    undo = faults.plant(fault)
    try:
        res = _run(small_root)
    finally:
        undo()
    assert not res["correct"], res["checks"]


def test_grid_holds_80640_scenarios_over_seven_ep_sizes():
    grid = load.scenario_grid(load.concrete_axes(
        json.loads(CONFIG.read_text())["grid"]))
    assert len(grid) == 80_640
    assert grid.ep_sizes == (1, 2, 4, 8, 16, 32, 64)
    grid.validate_axes()


def _reference():
    spec = run.resolve(ROOT, CELL)
    return spec["reference"], spec["config"]


def test_reference_matches_the_numpy_engine():
    """Every column, on four rows of each cluster and EP size, against
    ``sweep(backend="numpy")`` of the whole published grid."""
    from repro.core.sweep import sweep

    reference, config = _reference()
    axes = load.concrete_axes(config["grid"])
    cols = sweep(load.scenario_grid(axes), backend="numpy").columns
    ref = reference.Reference(config["model"], np.float64)
    rng = np.random.default_rng(7)
    for cluster in axes["clusters"]:
        for ep in axes["ep_sizes"]:
            rows = np.flatnonzero((cols["cluster"] == cluster)
                                  & (cols["ep_size"] == ep))
            for i in rng.choice(rows, 4, replace=False):
                want = ref.row(reference.scenario_at(axes, int(i)))
                for c in reference.LABEL_COLUMNS:
                    assert cols[c][i] == want[c], c
                for c in reference.NUMERIC_COLUMNS:
                    assert cols[c][i] == pytest.approx(want[c], rel=1e-12), c


@pytest.mark.parametrize("het,straggler", [("het:1x0.5+3x1.0", None),
                                           (None, "jitter:0.1")])
def test_reference_refuses_what_it_does_not_model(het, straggler):
    reference, config = _reference()
    s = {"workload": "llm:deepseek-v2-lite", "cluster": "v100-nvlink-ib",
         "n_workers": 64, "ep_size": 8, "policy": "tensorflow",
         "collective": "ring", "interconnect": None, "het": het,
         "straggler": straggler}
    with pytest.raises(ValueError, match="not modelled"):
        reference.Reference(config["model"]).row(s)
