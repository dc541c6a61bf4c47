"""The comparison that decides ``correct``.

After the window has closed, every sampled row that the timed path
returned is recomputed by the plain reference (:mod:`chipbench.reference`)
from the request that produced it, and compared:

* ``max_rel_err`` — the largest relative gap, over every sampled row and
  every numeric column (the kernel's columns and the tail columns, which
  without stragglers repeat the iteration time), between what the
  program returned and the reference;
* ``label_mismatches`` — sampled rows whose labels (workload, cluster,
  workers, policy, collective, interconnect, het, straggler, sync_k,
  faults, batch, method) differ from the scenario the reference puts at
  that row: a wrong row order or a wrong demultiplexing shows here;
* ``failed_requests`` — requests that raised or never returned an
  answer;
* ``rows_checked`` — at least one row has to be compared.

The limit of ``max_rel_err`` is the configuration's ``check`` entry.
With ``control=True`` the reference computed in float32 is put in the
program's place, which a sound limit must reject.
"""
from __future__ import annotations

import numpy as np

from chipbench.reference import (LABEL_COLUMNS, NUMERIC_COLUMNS, Reference,
                                 scenario_at)


def _rel(got: float, want: float) -> float:
    if want == got:
        return 0.0
    if want == 0.0 or not np.isfinite(got):
        return float("inf")
    return abs(got - want) / abs(want)


def compare(config: dict, records: list, control: bool = False) -> dict:
    """``{name: {"value": v, "limit": l}}`` for every number compared."""
    ref = Reference(config["model"], np.float64)
    low = Reference(config["model"], np.float32) if control else None
    worst, mismatches, rows = 0.0, 0, 0
    for rec in records:
        if rec.error is not None:
            continue
        for i, got in sorted(rec.rows.items()):
            s = scenario_at(rec.request.axes, i)
            want = ref.row(s)
            if low is not None:
                got = low.row(s)
            rows += 1
            if any(got.get(c) != want[c] for c in LABEL_COLUMNS):
                mismatches += 1
            for c in NUMERIC_COLUMNS:
                worst = max(worst, _rel(float(got[c]), want[c]))
    failed = sum(rec.error is not None for rec in records)
    return {
        "max_rel_err": {"value": worst,
                        "limit": config["check"]["max_rel_err"]},
        "label_mismatches": {"value": mismatches, "limit": 0},
        "failed_requests": {"value": failed, "limit": 0},
        "rows_checked": {"value": rows, "limit": 1},
    }


def passed(checks: dict) -> bool:
    """``rows_checked`` is a floor; every other number a ceiling."""
    return all(c["value"] >= c["limit"] if name == "rows_checked"
               else c["value"] <= c["limit"] for name, c in checks.items())
