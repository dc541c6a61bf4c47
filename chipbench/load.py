"""What every traffic driver shares.

A traffic mix is a JSON file of parameters, ``traffic/<traffic>.json``;
its ``mode`` names its driver, ``chipbench/drivers/<mode>.py``, found by
that name (``chipbench/run.py:resolve``).  A driver module exports

* ``Driver(config, traffic, seed)`` with ``setup()`` (warm every shape
  the window uses), ``one(index, record)`` (one request, its sampled
  answers kept in ``record.rows``), ``window(seconds, annotate)``
  (``(records, window_s)``) and ``close()``;
* ``request(config, traffic, seed, stream, index)``: request ``index``
  of ``stream`` of the mix under ``seed``, a :class:`Request`.

This module names no configuration and no mode.  Every draw comes from
``numpy.random.default_rng([seed, stream, index])`` (:func:`_rng`), so
one seed always gives the same requests and the same sampled rows.
"""
from __future__ import annotations

import math
import time
from contextlib import nullcontext
from dataclasses import dataclass, field, fields

import numpy as np

#: rng streams: warm-up requests, window requests, sampled rows.
WARM, WINDOW, ROWS = 1, 0, 2


@dataclass
class Request:
    """One request of the mix: grid axes in the reference's form (the
    configuration's ``axis_order`` and one list per axis named there)."""

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


def grid_size(axes: dict) -> int:
    return math.prod(len(axes[name]) for name in axes["axis_order"])


def interconnect_label(base: str, bw: float, lat: float) -> str:
    """The scaled-link spelling the program accepts and echoes:
    ``<base>@bw<F>@lat<F>``."""
    return f"{base}@bw{bw:g}@lat{lat:g}"


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


def sample_rows(n: int, k: int, seed: int, index: int) -> np.ndarray:
    """The rows of request ``index`` whose answers are checked."""
    return np.sort(_rng(seed, ROWS, index).choice(n, min(k, n),
                                                  replace=False))


def scenario_grid(axes: dict):
    """The program's ``ScenarioGrid`` for a request: every axis named in
    ``axes["axis_order"]`` is passed under that name.  A name that is not
    an axis of ``ScenarioGrid`` raises, so none is dropped unseen."""
    from repro.core.scenarios import ScenarioGrid

    known = {f.name for f in fields(ScenarioGrid)
             if isinstance(f.default, tuple)}
    unknown = [name for name in axes["axis_order"] if name not in known]
    if unknown:
        raise ValueError(f"ScenarioGrid has no axis {unknown}; "
                         f"its axes: {sorted(known)}")
    return ScenarioGrid(**{name: tuple(axes[name])
                           for name in axes["axis_order"]})


def _attempt(driver, index: int, rec: Record, annotate) -> None:
    """One request through ``driver``; a request that raises counts as
    failed, with its error kept."""
    with (annotate("chipbench.request") if annotate else nullcontext()):
        try:
            driver.one(index, rec)
        except Exception as exc:
            rec.t1 = time.perf_counter()
            rec.error = f"{type(exc).__name__}: {exc}"
