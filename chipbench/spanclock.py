"""Do the program's spans and the device share one clock?

    python3 chipbench/spanclock.py --workload frontier.sweep --seed 7 \\
        --seconds 20
    python3 chipbench/spanclock.py --workload frontier.sweep --seed 7 \\
        --sweeps 2 --out sweep_spans.xplane.pb.gz

Runs the cell's traffic after its warm-up, with the program's recorder
(``repro.core.obs``) on, under the profiler, for ``--seconds`` or for
``--sweeps`` requests, and prints one JSON line:

* ``kernels``: every ``jit__columns_jax`` module event on a device plane,
  and the share of them that lie inside a ``sweep.columns`` span of the
  trace and inside the ``sweep.columns.call`` … ``sweep.columns.wait``
  interval of the same span; ``min_margin_us`` is the least distance by
  which a kernel stays inside (negative: how far it sticks out);
* ``alignment``: how far the recorder's ``sweep.columns`` spans, moved
  onto the trace's clock as the per-layer readers move them
  (:func:`chipbench.recorder.on_trace_clock`), lie from the same spans
  as the trace recorded them, and the idle seconds each version gives;
* ``span_cost_us``: host µs per span entered and left, with the recorder
  off, on, and on under the profiler.

``--out`` keeps the trace (gzip of the ``.xplane.pb``).  Refuses to run
without a TPU, like ``chipbench/run.py``.
"""
from __future__ import annotations

import argparse
import gzip
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

ROOT = Path(__file__).resolve().parent.parent
if sys.path and Path(sys.path[0]).resolve() == ROOT / "chipbench":
    sys.path.pop(0)
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench import load, recorder, run, trace  # noqa: E402

KERNEL = "jit__columns_jax"
SPANS = ("sweep.columns", "sweep.columns.call", "sweep.columns.wait")


def kernel_placement(planes) -> list[dict]:
    """For every ``jit__columns_jax`` module event of a device plane, the
    ``sweep.columns`` span it overlaps most, and by how many µs the
    kernel stays inside that span (``columns_margin_us``) and inside the
    ``call`` … ``wait`` interval within it (``call_wait_margin_us``;
    ``None`` where that span has no ``wait``).  A negative margin is how
    far the kernel sticks out."""
    kernels = []
    for plane in planes:
        if trace.is_device_plane(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    kernels += [(ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9)
                                for ev in line.events
                                if trace._module_name(ev.name) == KERNEL]
    host = trace.reduce_planes(planes, SPANS)["host_spans"]
    columns = [(s, e) for n, s, e in host if n == "sweep.columns"]
    out = []
    for ks, ke in kernels:
        if not columns:
            out.append({"columns_margin_us": None,
                        "call_wait_margin_us": None})
            continue
        cs, ce = max(columns, key=lambda c: min(ke, c[1]) - max(ks, c[0]))
        inner = [(n, s, e) for n, s, e in host
                 if n != "sweep.columns" and cs <= s and e <= ce]
        calls = [s for n, s, _ in inner if n == "sweep.columns.call"]
        waits = [e for n, _, e in inner if n == "sweep.columns.wait"]
        out.append({
            "columns_margin_us": 1e6 * min(ks - cs, ce - ke),
            "call_wait_margin_us": (1e6 * min(ks - min(calls),
                                              max(waits) - ke)
                                    if calls and waits else None)})
    return out


def alignment(planes, program: dict) -> dict:
    """The recorder's ``sweep.columns`` spans as the readers place them
    on the trace's clock, against the same spans in the trace."""
    reduced = trace.reduce_planes(planes, [recorder.REQUEST, "sweep.columns"])
    traced = sorted((s, e) for n, s, e in reduced["host_spans"]
                    if n == "sweep.columns")
    reduced["host_spans"] = [h for h in reduced["host_spans"]
                             if h[0] == recorder.REQUEST]
    view = SimpleNamespace(program=program, reduced=reduced)
    mapped = sorted(recorder.on_trace_clock(view, "sweep.columns") or [])
    if not mapped or len(mapped) != len(traced):
        return {"paired": False, "mapped": len(mapped),
                "traced": len(traced)}
    worst = max(max(abs(a[0] - b[0]), abs(a[1] - b[1]))
                for a, b in zip(mapped, traced))
    dev = trace.fullest(reduced)
    gaps = reduced["devices"][dev]["gaps"] if dev else []
    return {"paired": True, "spans": len(mapped),
            "max_offset_us": worst * 1e6,
            "idle_s_mapped": recorder.idle_overlap(gaps, mapped),
            "idle_s_traced": recorder.idle_overlap(gaps, traced)}


def span_cost(obs, jax, n: int = 20000) -> dict:
    """Host µs per span entered and left: recorder off, on, and on while
    the profiler records."""

    def per_span():
        t = time.perf_counter()
        for _ in range(n):
            with obs.span("spanclock.probe"):
                pass
        return (time.perf_counter() - t) / n * 1e6

    obs.disable()
    off = per_span()
    obs.enable()
    on = per_span()
    scratch = tempfile.mkdtemp(prefix="spanclock-cost-")
    jax.profiler.start_trace(scratch)
    try:
        traced = per_span()
    finally:
        jax.profiler.stop_trace()
        obs.disable()
        obs.snapshot()
        shutil.rmtree(scratch, ignore_errors=True)
    return {"off": off, "on": on, "on_traced": traced}


def record(workload: str, seed: int, seconds: float | None = None,
           sweeps: int | None = None, out: str | None = None, *,
           require_tpu: bool = True) -> dict:
    spec = run.resolve(ROOT, workload)
    import jax

    from repro.core import obs
    from repro.launch.compile_cache import enable_compilation_cache

    device = run.device_info(jax, spec["cell"]["chips"], require_tpu)
    enable_compilation_cache()
    driver = spec["driver"].Driver(spec["config"], spec["traffic"], seed)
    trace_dir = tempfile.mkdtemp(prefix="spanclock-")
    try:
        driver.setup()
        obs.snapshot()
        obs.enable()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        try:
            if sweeps is None:
                records, _ = driver.window(seconds,
                                           jax.profiler.TraceAnnotation)
            else:
                records = []
                for i in range(sweeps):
                    req = spec["driver"].request(
                        spec["config"], spec["traffic"], seed, load.WINDOW, i)
                    rec = load.Record(req, t0=time.perf_counter())
                    with jax.profiler.TraceAnnotation(recorder.REQUEST):
                        driver.one(i, rec)
                    records.append(rec)
        finally:
            jax.profiler.stop_trace()
            obs.disable()
        program = obs.snapshot()
        path = sorted(Path(trace_dir).rglob("*.xplane.pb"))[-1]
        data = jax.profiler.ProfileData.from_file(str(path))
        planes = list(data.planes)        # valid while ``data`` lives
        placed = kernel_placement(planes)
        if out:
            Path(out).write_bytes(gzip.compress(path.read_bytes(), 9))
        result = {"device": device, "sweeps": len(records),
                  "alignment": alignment(planes, program)}
    finally:
        driver.close()
        shutil.rmtree(trace_dir, ignore_errors=True)
    margins = [p["columns_margin_us"] for p in placed
               if p["columns_margin_us"] is not None]
    inner = [p["call_wait_margin_us"] for p in placed
             if p["call_wait_margin_us"] is not None]
    result["kernels"] = {
        "count": len(placed),
        "inside_columns": sum(m >= 0 for m in margins) / max(len(placed), 1),
        "inside_call_wait": sum(m >= 0 for m in inner) / max(len(placed), 1),
        "min_margin_us": min(margins + inner, default=None)}
    result["span_cost_us"] = span_cost(obs, jax)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    group = ap.add_mutually_exclusive_group(required=True)
    group.add_argument("--seconds", type=float)
    group.add_argument("--sweeps", type=int)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        result = record(args.workload, args.seed, args.seconds, args.sweeps,
                        args.out)
    except run.NoChip as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
