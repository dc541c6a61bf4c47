"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload frontier.sweep --seed 7 --seconds 20 --trace 0

The cell, its configuration, its traffic mix and its metrics are found
by name from ``BENCHMARK.json`` at the checkout's root, and so is the
code that belongs to one configuration or one mix (:func:`resolve`):

* ``chipbench/references/<config>.py``, the configuration's plain
  reference, exporting ``Reference(model, dtype)`` with ``.row``,
  ``scenario_at``, ``LABEL_COLUMNS`` and ``NUMERIC_COLUMNS``
  (:mod:`chipbench.check`);
* ``chipbench/drivers/<mode>.py``, the driver of the mix's ``mode``,
  exporting ``Driver(config, traffic, seed)`` with ``setup``, ``one``,
  ``window`` and ``close``, and ``request`` (:mod:`chipbench.load`);
* ``chipbench/metrics/<metric>.py``, each metric's reader, exporting
  ``read(run_view)``.

One process holds the chip(s) and starts no child that touches JAX.
The run

1. refuses to run without a TPU or with fewer chips than the cell asks
   for, and names platform, ``device_kind`` and count;
2. turns on the persistent compile cache (``repro.launch.compile_cache``:
   ``JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``);
3. builds the cell's traffic and warms every shape the window uses;
4. measures a closed loop for ``--seconds``, counting compilations inside
   the window (there should be none);
5. compares sampled answers of the window with the configuration's
   plain reference, and
   prints one JSON line last: ``correct``, ``attempted``, ``failed``,
   ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
   ``checks``, each compared number beside its limit.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs
the same window under the profiler and host timers and reports its
per-layer metrics.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import resource  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
BENCH = "chipbench"
# run as a script, this file's directory heads sys.path; its modules
# (``trace``, ``check``) must not shadow the standard library's
if sys.path and Path(sys.path[0]).resolve() == ROOT / BENCH:
    sys.path.pop(0)
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import check, trace  # noqa: E402

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def info(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# Finding things by name.
# ----------------------------------------------------------------------
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def code(root: Path, kind: str, name: str):
    """The module ``<root>/chipbench/<kind>/<name>.py``: a metric's
    reader, a configuration's reference or a traffic mode's driver."""
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_')}",
        root / BENCH / kind / f"{name}.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def resolve(root: Path, workload: str) -> dict:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and metric entries, its configuration's
    reference module (``<root>/chipbench/references/<config>.py``) and
    its mix's driver module (``<root>/chipbench/drivers/<mode>.py``)."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"error: no cell {workload!r} in BENCHMARK.json; "
                         f"one of {sorted(cells)}")
    cell = cells[workload]

    def applies(metric):
        return workload in metric.get("workloads", [workload])

    config = load_json(root / BENCH / "configs" / f"{cell['config']}.json")
    traffic = load_json(root / BENCH / "traffic" / f"{cell['traffic']}.json")
    return {
        "cell": cell,
        "config": config,
        "traffic": traffic,
        "reference": code(root, "references", cell["config"]),
        "driver": code(root, "drivers", traffic["mode"]),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def reader(root: Path, name: str):
    """The module ``<root>/chipbench/metrics/<name>.py``."""
    return code(root, "metrics", name)


# ----------------------------------------------------------------------
# What the metric readers see.
# ----------------------------------------------------------------------
@dataclass
class RunView:
    records: list
    window_s: float
    setup_s: float
    timers: trace.HostTimers = field(default_factory=trace.HostTimers)
    reduced: dict | None = None
    trace_window_s: float | None = None

    def fullest_device(self) -> dict | None:
        if self.reduced is None:
            return None
        name = trace.fullest(self.reduced)
        return None if name is None else self.reduced["devices"][name]


class CompileCounter:
    """Programs obtained by the backend (compiled or read from the
    persistent cache) and persistent-cache hits, since the last reset."""

    def __init__(self):
        import jax

        self.compiles = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kwargs):
        if event == COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **kwargs):
        if event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    def reset(self):
        self.compiles = self.cache_hits = 0

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


class HostUsage:
    """What the host did to this process over a window: CPU seconds,
    involuntary context switches (time lost to other processes) and the
    garbage collector's passes and pause."""

    def __init__(self):
        self.gc_passes, self.gc_s, self._t = 0, 0.0, 0.0
        gc.callbacks.append(self._gc)
        self._start = resource.getrusage(resource.RUSAGE_SELF)

    def _gc(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.gc_passes += 1
            self.gc_s += time.perf_counter() - self._t

    def close(self) -> str:
        gc.callbacks.remove(self._gc)
        end = resource.getrusage(resource.RUSAGE_SELF)
        return (f"{end.ru_utime - self._start.ru_utime:.3f} s user, "
                f"{end.ru_stime - self._start.ru_stime:.3f} s system, "
                f"{end.ru_nivcsw - self._start.ru_nivcsw} involuntary "
                f"context switches; gc: {self.gc_passes} passes, "
                f"{self.gc_s * 1e3:.3f} ms")


def device_info(jax, chips: int, require_tpu: bool) -> dict:
    devices = jax.devices()
    dev = devices[0]
    found = f"{dev.platform} / {dev.device_kind} x {len(devices)}"
    if require_tpu and dev.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {found}")
    if require_tpu and len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX found {found}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def memory_peak(jax) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()]
    return int(max(peaks, default=0))


# ----------------------------------------------------------------------
# One run.
# ----------------------------------------------------------------------
def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             control: bool = False) -> dict:
    """Run the cell once and return the result document (the last line
    a run prints).  ``require_tpu=False`` and ``control=True`` exist for
    the tests and for ``chipbench/control.py``; the benchmark's own runs
    use neither."""
    spec = resolve(root, workload)
    cell, config, traffic = spec["cell"], spec["config"], spec["traffic"]
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    import jax

    device = device_info(jax, cell["chips"], require_tpu)
    info(f"device: {device['platform']} / {device['kind']} x "
         f"{device['count']}; cell {workload} asks for {cell['chips']}")
    from repro.launch.compile_cache import enable_compilation_cache

    info(f"compile cache: {enable_compilation_cache()}")
    counter = CompileCounter()
    driver = spec["driver"].Driver(config, traffic, seed)
    timers = trace.HostTimers(jax.profiler.TraceAnnotation
                              if traced else None)
    try:
        driver.setup()
        setup_s = time.perf_counter() - T_PROCESS
        info(f"set-up: {setup_s:.3f} s; programs {counter.compiles} "
             f"({counter.cache_hits} from the compile cache, "
             f"{counter.compiles - counter.cache_hits} compiled)")
        trace_dir = None
        if traced:
            for m in spec["per_layer"]:
                for name in getattr(reader(root, m["name"]), "TIMED", ()):
                    timers.install(name)
            for name in timers.missing:
                info(f"timer: {name} not found; its metrics are left out")
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0  # annotations only: less overhead
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        counter.reset()
        usage = HostUsage()
        t_trace = time.perf_counter()
        try:
            records, window_s = driver.window(
                seconds, jax.profiler.TraceAnnotation if traced else None)
        finally:
            trace_window_s = time.perf_counter() - t_trace
            host = usage.close()
            if traced:
                jax.profiler.stop_trace()
            timers.uninstall()
        in_window = counter.compiles
        device["memory_peak_bytes"] = memory_peak(jax)
    finally:
        driver.close()
        counter.close()
    sizes = sorted({r.request.size for r in records})
    failed = sum(r.error is not None for r in records)
    info(f"window: {window_s:.3f} s, {len(records)} requests of "
         f"{sizes} scenarios, {failed} failed; compilations inside the "
         f"window: {in_window}")
    for r in records:
        if r.error is not None:
            info(f"failed request: {r.error}")
            break
    lat = sorted(r.latency_s * 1e3 for r in records)
    info(f"request ms: min {lat[0]:.3f}, median {lat[len(lat) // 2]:.3f}, "
         f"p90 {lat[len(lat) * 9 // 10]:.3f}, max {lat[-1]:.3f}")
    info(f"host over the window: {host}")

    view = RunView(records, window_s, setup_s, timers)
    result = {}
    if traced:
        files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
        view.reduced = trace.reduce_xspace(
            str(files[-1]), ["chipbench.request", *timers.spans])
        shutil.rmtree(trace_dir, ignore_errors=True)
        view.trace_window_s = trace_window_s
        devs = view.reduced["devices"]
        used = [d["busy_s"] for d in devs.values()]
        device["busy_s"] = sum(used) / len(used) if used else 0.0
        device["window_s"] = trace_window_s
        result["breakdown"] = trace.breakdown(view.reduced)
        info(f"trace: {len(devs)} device plane(s), busy "
             f"{[round(b, 6) for b in used]} s of {trace_window_s:.3f} s")

    metrics = {}
    for m in spec["per_layer" if traced else "end_to_end"]:
        value = reader(root, m["name"]).read(view)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t_check = time.perf_counter()
    checks = check.compare(spec["reference"], config, records,
                           control=control)
    checks["compiles_in_window"] = {"value": in_window, "limit": 0}
    correct = check.passed(checks)
    info(f"check: {checks['rows_checked']['value']} rows against the "
         f"reference in {time.perf_counter() - t_check:.2f} s")
    for name, c in checks.items():
        info(f"check {name}: {c['value']} (limit {c['limit']})")
    return {"correct": correct, "attempted": len(records), "failed": failed,
            "metrics": metrics, "device": device, **result,
            "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as exc:
        info(f"error: {exc}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
