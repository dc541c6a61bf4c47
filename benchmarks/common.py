"""Shared benchmark plumbing: ``name,us_per_call,derived`` CSV rows and
a median wall-time helper.  (The persistent jax compilation cache is
:func:`repro.launch.compile_cache.enable_compilation_cache`.)"""
from __future__ import annotations

import time
from typing import Callable


def row(name: str, us_per_call: float, derived: str = "") -> str:
    line = f"{name},{us_per_call:.3f},{derived}"
    print(line, flush=True)
    return line


def time_call(fn: Callable, repeats: int = 5) -> float:
    """Median wall-time of fn() in microseconds."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6
