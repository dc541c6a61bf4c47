"""The comparison that decides ``correct``.

After the window has closed, every sampled row that the timed path
returned is recomputed by the configuration's own plain reference and
compared.  That reference is ``chipbench/references/<config>.py``, found
by the configuration's name (``chipbench/run.py:resolve``), and exports
``Reference(model, dtype)`` with ``.row(scenario)``,
``scenario_at(axes, i)`` (the scenario at row ``i`` of a request),
``LABEL_COLUMNS`` and ``NUMERIC_COLUMNS``.  This module imports no
reference and names no configuration.  The numbers compared:

* ``max_rel_err`` — the largest relative gap, over every sampled row and
  every numeric column (the reference's ``NUMERIC_COLUMNS``), between
  what the program returned and the reference;
* ``label_mismatches`` — sampled rows whose labels (the reference's
  ``LABEL_COLUMNS``) differ from those of the scenario the reference
  puts at that row: a wrong row order or a wrong demultiplexing shows
  here;
* ``failed_requests`` — requests that raised or never returned an
  answer;
* ``rows_checked`` — at least one row has to be compared.

The limit of ``max_rel_err`` is the configuration's ``check`` entry.
With ``control=True`` the same reference computed in float32,
``Reference(model, np.float32)``, is put in the program's place, which a
sound limit must reject.
"""
from __future__ import annotations

import numpy as np


def _rel(got: float, want: float) -> float:
    if want == got:
        return 0.0
    if want == 0.0 or not np.isfinite(got):
        return float("inf")
    return abs(got - want) / abs(want)


def compare(reference, config: dict, records: list,
            control: bool = False) -> dict:
    """``{name: {"value": v, "limit": l}}`` for every number compared;
    ``reference`` is the configuration's reference module."""
    ref = reference.Reference(config["model"], np.float64)
    low = reference.Reference(config["model"], np.float32) \
        if control else None
    worst, mismatches, rows = 0.0, 0, 0
    for rec in records:
        if rec.error is not None:
            continue
        for i, got in sorted(rec.rows.items()):
            s = reference.scenario_at(rec.request.axes, i)
            want = ref.row(s)
            if low is not None:
                got = low.row(s)
            rows += 1
            if any(got.get(c) != want[c] for c in reference.LABEL_COLUMNS):
                mismatches += 1
            for c in reference.NUMERIC_COLUMNS:
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
