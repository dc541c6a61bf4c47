"""The driver of traffic mode ``"sweep"``: one planner runs whole-grid
``sweep(grid, backend="jax")`` calls back to back.

With ``link_factors`` every sweep is a new frontier: each factor axis is
drawn log-uniform in ``[low, high]`` at ``digits`` significant digits,
distinct within the axis, so the grid keeps its shape and only its
numbers change.  What a driver module exports is set out in
:mod:`chipbench.load`.
"""
from __future__ import annotations

import time

from chipbench.load import (WARM, WINDOW, Record, Request, _attempt, _rng,
                            concrete_axes, draw_factors, sample_rows,
                            scenario_grid)


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


class Driver:
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
