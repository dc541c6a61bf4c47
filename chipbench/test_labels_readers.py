"""Tests of the readers of the program's ``sweep.labels`` span and
``sweep.labels_overlapped`` counter, on the CPU."""
from __future__ import annotations

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [p for p in (str(ROOT), str(ROOT / "src")) if p not in sys.path]

from chipbench import recorder, run  # noqa: E402
from chipbench.test_chipbench import _run  # noqa: E402
from chipbench.test_chipbench import tiny_root  # noqa: E402,F401  (fixture)

from repro.core import obs  # noqa: E402
from repro.core.obs import SpanRecord  # noqa: E402

READERS = ("labels_ms.sweep", "labels_overlapped.sweep")


@pytest.fixture(autouse=True)
def fresh_recorder(monkeypatch):
    """Each test starts with the recorder off and not yet armed."""
    monkeypatch.setattr(recorder, "_recorder", {"obs": None, "taken": False})
    obs.disable()
    obs.snapshot()
    yield
    obs.disable()
    obs.snapshot()


def _bare_view():
    return run.RunView(records=[], window_s=1.0, setup_s=1.0)


@pytest.mark.parametrize("name", READERS)
def test_reader_without_the_recorders_data_is_none(name):
    assert run.reader(ROOT, name).read(_bare_view()) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_of_a_program_without_the_span_or_counter_is_none(name):
    """A program that records but gathers no labels under its kernel
    (one from before the span and counter) gives nothing, not 0."""
    view = _bare_view()
    view.program = {
        "spans": [SpanRecord("sweep", None, 1, 0.0, 0.05),
                  SpanRecord("sweep.columns", "sweep", 1, 0.01, 0.04)],
        "counters": {"sweep.builds": 1}}
    assert run.reader(ROOT, name).read(view) is None


def test_readers_divide_by_the_sweeps_recorded():
    def rec(name, parent, t0, t1):
        return SpanRecord(name, parent, 1, t0, t1)

    view = _bare_view()
    view.program = {
        "spans": [rec("sweep", None, 0.0, 0.050),
                  rec("sweep.labels", "sweep.columns", 0.01, 0.014),
                  rec("sweep", None, 0.1, 0.14),
                  rec("sweep.labels", "sweep.columns", 0.11, 0.116)],
        "counters": {"sweep.labels_overlapped": 2}}
    read = {name: run.reader(ROOT, name).read(view) for name in READERS}
    assert read["labels_ms.sweep"] == pytest.approx(5.0)
    assert read["labels_overlapped.sweep"] == 1.0


def test_a_traced_run_reads_the_labels_gathered_under_the_kernel(tiny_root):
    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in READERS + ("columns_ms.sweep", "wait_ms.sweep"):
            m["workloads"].append("tiny.sweep")
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))
    res = _run(tiny_root, "tiny.sweep", traced=True)
    assert res["correct"], res["checks"]
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert got["labels_overlapped.sweep"] == 1.0
    assert 0 < got["labels_ms.sweep"] < got["columns_ms.sweep"]
    assert got["labels_ms.sweep"] + got["wait_ms.sweep"] \
        <= got["columns_ms.sweep"]
