"""Host timers and the reduction from a profiler trace to device numbers.

Used only by ``--trace 1`` runs; end-to-end numbers come from runs with
all of this off.

* :class:`HostTimers` wraps program functions found by dotted name
  (``module:Class.method`` or ``module:function``) and records the
  seconds of every call, under a profiler annotation of the same name so
  the spans also land in the trace.  A name that no longer resolves is
  reported as missing, never as a zero.
* :func:`reduce_xspace` turns a ``.xplane.pb`` file into per-device
  busy time (the union of the intervals in which an XLA op ran), time
  per XLA module (``jit__columns_jax`` is the sweep kernel), the ops that
  took most time and the longest idle gaps by what the host was doing.
"""
from __future__ import annotations

import functools
import importlib
import re
import time
from collections import defaultdict


class HostTimers:
    """Wall-clock spans of named program functions, per call."""

    def __init__(self, annotate=None):
        self.spans: dict[str, list[float]] = {}
        self.missing: list[str] = []
        self._undo: list = []
        self._annotate = annotate

    def install(self, name: str) -> None:
        if name in self.spans or name in self.missing:
            return
        module_name, _, attr = name.partition(":")
        try:
            owner = importlib.import_module(module_name)
            *path, leaf = attr.split(".")
            for part in path:
                owner = getattr(owner, part)
            original = getattr(owner, leaf)
        except (ImportError, AttributeError):
            self.missing.append(name)
            return
        spans = self.spans[name] = []
        annotate = self._annotate

        @functools.wraps(original)
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            try:
                if annotate is None:
                    return original(*args, **kwargs)
                with annotate(name):
                    return original(*args, **kwargs)
            finally:
                spans.append(time.perf_counter() - t0)

        setattr(owner, leaf, timed)
        self._undo.append((owner, leaf, original))

    def uninstall(self) -> None:
        for owner, leaf, original in reversed(self._undo):
            setattr(owner, leaf, original)
        self._undo.clear()


def _module_name(event_name: str) -> str:
    """``jit__columns_jax(123)`` -> ``jit__columns_jax``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def _op_name(event_name: str) -> str:
    """``%fusion.7 = f32[2160]{...} fusion(...)`` -> ``%fusion.7``: the
    TPU trace names an op by its whole HLO instruction."""
    return event_name.split(" = ", 1)[0]


def _union(intervals) -> tuple[float, list[tuple[float, float]]]:
    """Total length of the union of ``(start, end)`` intervals, and the
    merged intervals in order."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return sum(e - s for s, e in merged), [(s, e) for s, e in merged]


def is_device_plane(name: str) -> bool:
    return re.fullmatch(r"/device:(TPU|GPU):\d+", name) is not None


def reduce_planes(planes, host_span_names=()) -> dict:
    """The reduction over an iterable of planes, each with ``name`` and
    ``lines``, each line with ``name`` and ``events``, each event with
    ``name``, ``start_ns`` and ``duration_ns`` (the shape of
    ``jax.profiler.ProfileData``).

    Returns ``{"devices": {plane: {"busy_s", "first_s", "last_s",
    "modules": {name: s}, "ops": {name: s}, "gaps": [(start, end)]}},
    "host_spans": [(name, start_s, end_s)]}``; times are seconds on the
    trace's clock."""
    devices, host = {}, []
    wanted = set(host_span_names)
    for plane in planes:
        if is_device_plane(plane.name):
            ops, modules, intervals = defaultdict(float), defaultdict(float), []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    for ev in line.events:
                        start, dur = ev.start_ns * 1e-9, ev.duration_ns * 1e-9
                        ops[_op_name(ev.name)] += dur
                        intervals.append((start, start + dur))
                elif line.name == "XLA Modules":
                    for ev in line.events:
                        modules[_module_name(ev.name)] += ev.duration_ns * 1e-9
            busy, merged = _union(intervals)
            gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
            devices[plane.name] = {
                "busy_s": busy, "modules": dict(modules), "ops": dict(ops),
                "first_s": merged[0][0] if merged else None,
                "last_s": merged[-1][1] if merged else None,
                "gaps": gaps}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in wanted:
                        start = ev.start_ns * 1e-9
                        host.append((ev.name, start,
                                     start + ev.duration_ns * 1e-9))
    return {"devices": devices, "host_spans": host}


def reduce_xspace(path: str, host_span_names=()) -> dict:
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    return reduce_planes(data.planes, host_span_names)


def fullest(reduced: dict) -> str | None:
    """The device plane with the most busy time."""
    devs = reduced["devices"]
    return max(devs, key=lambda d: devs[d]["busy_s"]) if devs else None


def breakdown(reduced: dict, top: int = 10) -> dict:
    """The fullest device's costliest ops, and its idle time grouped by
    the innermost host span open at the middle of each gap."""
    dev = fullest(reduced)
    if dev is None:
        return {"device_ops": [], "idle_gaps": []}
    d = reduced["devices"][dev]
    ops = sorted(d["ops"].items(), key=lambda kv: -kv[1])[:top]
    spans = sorted(reduced["host_spans"], key=lambda sp: sp[1])
    idle, active, nxt = defaultdict(float), [], 0
    for start, end in d["gaps"]:                 # gaps come in time order
        mid = 0.5 * (start + end)
        while nxt < len(spans) and spans[nxt][1] <= mid:
            active.append(spans[nxt])
            nxt += 1
        active = [sp for sp in active if sp[2] >= mid]
        label = min(active, key=lambda sp: sp[2] - sp[1])[0] \
            if active else "between requests"
        idle[label] += end - start
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
