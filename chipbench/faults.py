"""Faults planted under the timed path, which the check must catch.

Each fault replaces a method of the program's ``JaxGridEvaluator``
where the answers are produced, and :func:`plant` returns the function
that removes it again.  The benchmark's own runs plant nothing; the
tests and ``chipbench/control.py`` do.

* ``stale`` — a sweep returns the previous sweep's answer unchanged;
* ``half`` — the second half of the rows is left out: the kernel's
  first-half answers stand in for it;
* ``exchange`` — the gather between chips is left out: only the first
  of four shards comes back, repeated over the others;
* ``altered`` — every answer's iteration time is altered by one part in
  a million where the kernel produces it.
"""
from __future__ import annotations

import numpy as np

FAULTS = ("stale", "half", "exchange", "altered")


def _repeat_head(cols: dict, parts: int) -> dict:
    out = {}
    for k, v in cols.items():
        head = v[:max(1, -(-len(v) // parts))]
        out[k] = np.resize(head, v.shape)
    return out


def plant(name: str):
    """Plant fault ``name``; returns the function that removes it."""
    from repro.core.batched_jax import JaxGridEvaluator

    if name == "stale":
        attr = "run"
        original = JaxGridEvaluator.run
        last: dict = {}

        def patched(self, params=None, seed=0):
            fresh = original(self, params, seed)
            previous = last.get(len(self), fresh)
            last[len(self)] = fresh
            return previous
    else:
        attr = "columns"
        original = JaxGridEvaluator.columns

        def patched(self, params=None):
            cols = original(self, params)
            if name == "half":
                return _repeat_head(cols, 2)
            if name == "exchange":
                return _repeat_head(cols, 4)
            if name == "altered":
                return {**cols, "iteration_time_s":
                        cols["iteration_time_s"] * (1.0 + 1e-6)}
            raise ValueError(f"unknown fault {name!r}; one of {FAULTS}")

    setattr(JaxGridEvaluator, attr, patched)
    return lambda: setattr(JaxGridEvaluator, attr, original)
