"""The one traffic generator and its driver.

A traffic mix is a JSON file of parameters; ``mode`` picks the driver
(``DRIVERS``):

* ``"sweep"`` — one planner runs whole-grid ``sweep(grid, backend="jax")``
  calls back to back.  With ``link_factors`` every sweep is a new
  frontier: each factor axis is drawn log-uniform in ``[low, high]`` at
  ``digits`` significant digits, distinct within the axis, so the grid
  keeps its shape and only its numbers change.

Every draw comes from ``numpy.random.default_rng([seed, stream, index])``,
so one seed always gives the same requests and the same sampled rows.
"""
from __future__ import annotations

import math
import time
from contextlib import nullcontext
from dataclasses import dataclass, field

import numpy as np

from chipbench.reference import grid_size, interconnect_label

#: rng streams: warm-up requests, window requests, sampled rows.
WARM, WINDOW, ROWS = 1, 0, 2


@dataclass
class Request:
    """One request of the mix: grid axes in the reference's form (the
    configuration's ``axis_order`` plus a concrete ``interconnects``
    list)."""

    axes: dict

    @property
    def size(self) -> int:
        return grid_size(self.axes)


@dataclass
class Record:
    """What the window saw of one request."""

    request: Request
    t0: float
    t1: float = 0.0
    error: str | None = None
    rows: dict = field(default_factory=dict)     # row index -> {column: value}

    @property
    def latency_s(self) -> float:
        return self.t1 - self.t0


def _rng(seed: int, stream: int, index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream, int(index)])


def draw_factors(rng: np.random.Generator, spec: dict, digits: int) -> list:
    """``spec["count"]`` distinct factors, log-uniform in
    ``[spec["low"], spec["high"]]``, rounded to ``digits`` significant
    digits, ascending."""
    lo, hi = math.log(spec["low"]), math.log(spec["high"])
    while True:
        vals = sorted(float(f"{math.exp(x):.{digits}g}")
                      for x in rng.uniform(lo, hi, spec["count"]))
        if len(set(vals)) == len(vals):
            return vals


def concrete_axes(grid: dict, factors: dict | None = None) -> dict:
    """The configuration's grid with its link frontier spelled out as an
    ``interconnects`` axis (``factors`` overrides the published
    ``bw_factors``/``lat_factors``)."""
    axes = dict(grid)
    if "link_bases" in grid:
        f = {**{k: grid[k] for k in ("bw_factors", "lat_factors")},
             **(factors or {})}
        axes.update(f)
        axes["interconnects"] = [interconnect_label(b, bw, lat)
                                 for b in grid["link_bases"]
                                 for bw in f["bw_factors"]
                                 for lat in f["lat_factors"]]
    return axes


def _fresh_factors(rng, traffic: dict) -> dict:
    spec = traffic["link_factors"]
    return {name: draw_factors(rng, spec[name], spec["digits"])
            for name in ("bw_factors", "lat_factors")}


def request(config: dict, traffic: dict, seed: int, stream: int,
            index: int) -> Request:
    """Request ``index`` of ``stream`` of the mix under ``seed``."""
    rng = _rng(seed, stream, index)
    factors = _fresh_factors(rng, traffic) \
        if "link_factors" in traffic else None
    return Request(concrete_axes(config["grid"], factors))


def sample_rows(n: int, k: int, seed: int, index: int) -> np.ndarray:
    """The rows of request ``index`` whose answers are checked."""
    return np.sort(_rng(seed, ROWS, index).choice(n, min(k, n),
                                                  replace=False))


# ----------------------------------------------------------------------
# Program adapters: a request as the program takes it.
# ----------------------------------------------------------------------
def scenario_grid(axes: dict):
    """The program's ``ScenarioGrid`` for a request."""
    from repro.core.scenarios import ScenarioGrid

    return ScenarioGrid(workloads=tuple(axes["workloads"]),
                        clusters=tuple(axes["clusters"]),
                        worker_counts=tuple(axes["worker_counts"]),
                        policies=tuple(axes["policies"]),
                        collectives=tuple(axes["collectives"]),
                        interconnects=tuple(axes["interconnects"]),
                        het_profiles=tuple(axes["het_profiles"]),
                        stragglers=tuple(axes["stragglers"]))


# ----------------------------------------------------------------------
# Drivers.
# ----------------------------------------------------------------------
class SweepDriver:
    """Closed-loop whole-grid sweeps through ``sweep(backend="jax")``."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed

    def _call(self, req: Request) -> dict:
        from repro.core.sweep import sweep

        return sweep(scenario_grid(req.axes), backend="jax").columns

    def setup(self) -> None:
        for k in range(self.traffic["warmup_requests"]):
            self._call(request(self.config, self.traffic, self.seed, WARM, k))

    def one(self, index: int, rec: Record) -> None:
        cols = self._call(rec.request)
        rec.t1 = time.perf_counter()
        for i in sample_rows(rec.request.size,
                             self.traffic["rows_checked_per_request"],
                             self.seed, index):
            rec.rows[int(i)] = {c: v[i].item() if hasattr(v[i], "item")
                                else v[i] for c, v in cols.items()}

    def window(self, seconds: float, annotate=None):
        """Closed loop for ``seconds``: each sweep starts when the previous
        one has returned; the window ends with the sweep that crosses
        ``seconds``."""
        records = []
        t_start = time.perf_counter()
        index = 0
        while True:
            req = request(self.config, self.traffic, self.seed, WINDOW, index)
            rec = Record(req, t0=time.perf_counter())
            _attempt(self, index, rec, annotate)
            records.append(rec)
            index += 1
            if rec.t1 - t_start >= seconds:
                return records, rec.t1 - t_start

    def close(self) -> None:
        pass


DRIVERS = {"sweep": SweepDriver}


def _attempt(driver, index: int, rec: Record, annotate) -> None:
    """One request through ``driver``; a request that raises counts as
    failed, with its error kept."""
    with (annotate("chipbench.request") if annotate else nullcontext()):
        try:
            driver.one(index, rec)
        except Exception as exc:
            rec.t1 = time.perf_counter()
            rec.error = f"{type(exc).__name__}: {exc}"
