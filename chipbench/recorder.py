"""The program's own spans and counters, as the per-layer readers see
them.

The program (``repro.core.obs``) records spans and counters only while
its recorder is on.  The harness gathers each reader's ``TIMED`` once
before a traced window and calls ``read(run)`` after it:

* :data:`ARM`, used as a reader's ``TIMED``, names no function to time;
  gathering it turns the recorder on, once per process;
* :func:`recorded` takes what the window recorded at the first read,
  turns the recorder off, and keeps it on the run as ``run.program``.

A program without the recorder leaves both with nothing, and every
reader built on them returns ``None``.

The program's times are ``time.perf_counter`` seconds and the trace has
a clock of its own, a constant offset away.  In a sweep cell every
request is one ``sweep()`` call, in order, and the ``chipbench.request``
annotation opens before its ``sweep`` span, so the offset is at least
(request start - sweep start) for every request; :func:`on_trace_clock`
takes the largest of these, which is short of the true offset by the
least time any request spends before its sweep starts (tens of µs).
"""
from __future__ import annotations

from chipbench import trace

REQUEST = "chipbench.request"
ROOT = "sweep"

_recorder = {"obs": None, "taken": False}


def arm() -> None:
    """Turn the program's recorder on, unless it is already on, has
    already been read, or the program has none."""
    if _recorder["obs"] is not None or _recorder["taken"]:
        return
    try:
        from repro.core import obs
    except ImportError:
        return
    obs.snapshot()
    obs.enable()
    _recorder["obs"] = obs


class _Arm:
    """A reader's ``TIMED``: turns the recorder on when the harness
    gathers it before the traced window, and names nothing to time."""

    def __iter__(self):
        arm()
        return iter(())


ARM = _Arm()


def recorded(run) -> dict | None:
    """``{"spans": [...], "counters": {...}}`` that the program recorded
    in ``run``'s window, or ``None`` where it recorded nothing."""
    data = getattr(run, "program", None)
    obs = _recorder["obs"]
    if data is None and obs is not None:
        data = obs.snapshot()
        obs.disable()
        _recorder.update(obs=None, taken=True)
        run.program = data
    return data


def _roots(data: dict) -> list:
    return sorted((s for s in data["spans"]
                   if s.name == ROOT and s.parent is None),
                  key=lambda s: s.t0)


def ms_per_sweep(run, name: str) -> float | None:
    """Host ms per sweep in the spans called ``name``."""
    data = recorded(run)
    if data is None:
        return None
    spans = [s for s in data["spans"] if s.name == name]
    sweeps = len(_roots(data))
    if not spans or not sweeps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in spans) / sweeps


def per_sweep(run, counter: str) -> float | None:
    """The counter ``counter`` per sweep."""
    data = recorded(run)
    if data is None or counter not in data["counters"]:
        return None
    sweeps = len(_roots(data))
    return data["counters"][counter] / sweeps if sweeps else None


def on_trace_clock(run, name: str) -> list | None:
    """The spans called ``name`` as ``(start, end)`` on the trace's
    clock, or ``None`` where there is no trace, no recording, or the
    requests and sweeps do not pair one to one."""
    data = recorded(run)
    if data is None or run.reduced is None:
        return None
    requests = sorted((s, e) for n, s, e in run.reduced["host_spans"]
                      if n == REQUEST)
    roots = _roots(data)
    if not roots or len(roots) != len(requests):
        return None
    offset = max(a - r.t0 for r, (a, _) in zip(roots, requests))
    return [(s.t0 + offset, s.t1 + offset)
            for s in data["spans"] if s.name == name]


def idle_overlap(gaps, intervals) -> float:
    """Seconds of the device's idle ``gaps`` that the union of the host
    ``intervals`` covers: each gap is split by its overlap, not given
    whole to the span open at its middle."""
    _, spans = trace._union(intervals)
    total, i, j = 0.0, 0, 0
    gaps = sorted(gaps)
    while i < len(gaps) and j < len(spans):
        lo = max(gaps[i][0], spans[j][0])
        hi = min(gaps[i][1], spans[j][1])
        if hi > lo:
            total += hi - lo
        if gaps[i][1] < spans[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_pct(run, name: str) -> float | None:
    """Share of the traced window, in %, in which the fullest device is
    idle while a span called ``name`` is open."""
    dev = run.fullest_device()
    spans = on_trace_clock(run, name)
    if dev is None or not spans or not run.trace_window_s:
        return None
    return 100.0 * idle_overlap(dev["gaps"], spans) / run.trace_window_s
