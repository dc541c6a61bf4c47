"""Measurement runner / CLI: ``python -m repro.measure --arch <id>``.

Measures in process, on the devices jax finds:

* on a TPU host, the chips there, at the architecture's published
  width and its own dtype with only the depth cut
  (:data:`CHIP_GEOMETRY`);
* on a CPU host, forced host CPU devices at a reduced f32 width
  (:class:`Geometry` defaults; ``--smoke`` for the tiny CI preset).
  jax fixes the host device count when its backend starts, so
  :func:`main` puts ``--xla_force_host_platform_device_count`` into
  ``XLA_FLAGS`` (:mod:`repro.launch.hostdev`) before any device is
  queried; a chip host ignores the flag.

Two artifacts land in the measurement directory:

* ``<arch>.trace`` — the paper-format per-layer trace the ``jax:``
  workload provider serves (sweepable like any other workload);
* ``<arch>.json`` — the full harvest: the device it ran on, the
  geometry, per-policy measured step times and losses, HLO collective
  bytes + cross-checks, the alpha-beta collective fit, segmentation
  metadata.

:func:`measure_in_subprocess` is the CPU rehearsal path for tests and
benchmarks whose own process already holds jax: a child pinned to the
CPU platform with forced host devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
from pathlib import Path

from repro.launch.hostdev import child_env, force_host_device_count

#: Decoder-only archs the explicit-DP step can train as-is (the
#: encoder-decoder and vision archs need extra batch inputs the ddp
#: runtime doesn't carry).
MEASURABLE_ARCHS = (
    "gemma3-1b", "grok-1-314b", "internlm2-20b", "qwen1.5-32b",
    "qwen1.5-4b", "qwen2-moe-a2.7b", "recurrentgemma-2b", "rwkv6-1.6b",
)

#: Width fields of :class:`Geometry`; all ``None`` means the
#: architecture's published width.
WIDTH_FIELDS = ("d_model", "num_heads", "d_ff", "vocab_size")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Model/measurement geometry.  Width fields set: the f32
    ``reduced()`` variant at those widths (host-CPU feasible); all
    ``None``: the published width in the architecture's own dtype.
    ``n_devices=None`` takes every visible device."""

    num_layers: int = 8
    d_model: int | None = 256
    num_heads: int | None = 4
    d_ff: int | None = 512
    vocab_size: int | None = 1024
    seq_len: int = 64
    batch_per_gpu: int = 4
    n_devices: int | None = 2
    repeats: int = 5
    step_iters: int = 8


SMOKE_GEOMETRY = Geometry(num_layers=4, d_model=128, d_ff=256,
                          vocab_size=512, seq_len=32, batch_per_gpu=2,
                          repeats=3, step_iters=4)

#: The TPU preset: published width, depth cut to 4 layers, 1k-token
#: sequences, every chip of the host.
CHIP_GEOMETRY = Geometry(num_layers=4, d_model=None, num_heads=None,
                         d_ff=None, vocab_size=None, seq_len=1024,
                         batch_per_gpu=2, n_devices=None, repeats=3,
                         step_iters=3)


def default_out_dir() -> str:
    from repro.core.workloads import JaxProvider

    return JaxProvider.measure_dir()


def measured_config(arch: str, geometry: Geometry):
    """The :class:`~repro.models.common.ModelConfig` a geometry
    measures: the published config with only ``num_layers`` cut when
    every width field is ``None``, else its f32 ``reduced()`` variant."""
    from repro.configs import get_config

    cfg = get_config(arch)
    widths = {f: getattr(geometry, f) for f in WIDTH_FIELDS}
    if all(v is None for v in widths.values()):
        return dataclasses.replace(
            cfg, name=f"{cfg.name}-{geometry.num_layers}l",
            num_layers=geometry.num_layers).validate()
    unset = [f for f, v in widths.items() if v is None]
    if unset:
        raise ValueError(f"geometry sets some widths but not {unset}: "
                         f"set all of {WIDTH_FIELDS} or none")
    return cfg.reduced(num_layers=geometry.num_layers, **widths)


def run_measurement(arch: str, out_dir: str | Path,
                    geometry: Geometry,
                    policies: tuple[str, ...] | None = None) -> dict:
    """Measure ``arch`` in process on the visible devices (on a CPU
    host the device count must already be forced), write
    ``<arch>.trace`` + ``<arch>.json`` into ``out_dir``, and return the
    JSON document."""
    import jax

    from repro.measure import calibrate
    from repro.measure.harness import MEASURED_SYNC_POLICIES, measure_model
    from repro.traces.format import write_trace

    g = geometry
    if g.n_devices is None:
        g = dataclasses.replace(g, n_devices=len(jax.devices()))
    cfg = measured_config(arch, g)
    run = measure_model(
        cfg, arch=arch, n_devices=g.n_devices,
        batch_per_gpu=g.batch_per_gpu, seq_len=g.seq_len,
        policies=policies or MEASURED_SYNC_POLICIES,
        repeats=g.repeats, step_iters=g.step_iters)

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    trace_path = out_dir / f"{arch}.trace"
    write_trace(run.trace, trace_path)

    latency, bandwidth = calibrate.fit_alpha_beta(run.allreduce_samples)
    # one device needs no gradient traffic, and XLA's TPU pipeline drops
    # single-participant all-reduces, so the bytes cross-check is a
    # multi-device check
    checks = calibrate.crosscheck_collective_bytes(
        cfg, run.collective_stats) if g.n_devices > 1 else {}
    dev = jax.devices()[0]
    doc = dict(run.summary())
    doc.update({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": g.n_devices},
        "geometry": dataclasses.asdict(g),
        "dtype": jax.numpy.dtype(cfg.dtype).name,
        "workload": f"jax:{arch}",
        "trace_path": str(trace_path),
        "allreduce_fit": {"latency_s": latency,
                          "bandwidth_bytes_per_s": bandwidth},
        "bytes_crosscheck": {
            pol: {"hlo_bytes": c.hlo_bytes,
                  "expected_bytes": c.expected_bytes,
                  "rel_err": c.rel_err}
            for pol, c in checks.items()},
    })
    (out_dir / f"{arch}.json").write_text(json.dumps(doc, indent=2))
    return doc


#: Geometry field -> CLI flag; everything not listed here is the field
#: name with underscores dashed (the one derivation shared by the
#: parser and the subprocess command builder).
_FLAG_OVERRIDES = {"n_devices": "--devices"}


def _geometry_flag(field_name: str) -> str:
    return _FLAG_OVERRIDES.get(field_name,
                               "--" + field_name.replace("_", "-"))


def measure_in_subprocess(arch: str, *, out_dir: str | Path,
                          geometry: Geometry = SMOKE_GEOMETRY,
                          policies: tuple[str, ...] | None = None,
                          timeout: float = 1800) -> dict:
    """Measure ``arch`` in a CPU child and return its JSON document —
    the CPU rehearsal for benchmarks/tests whose own process already
    holds jax.  The child runs a reduced width only: the published
    width is measured in process on a chip host."""
    if None in (getattr(geometry, f) for f in WIDTH_FIELDS + ("n_devices",)):
        raise ValueError("measure_in_subprocess runs reduced widths on "
                         "forced CPU devices; set every width field and "
                         "n_devices")
    argv = ["--arch", arch, "--out-dir", str(out_dir)]
    for f in dataclasses.fields(Geometry):
        argv += [_geometry_flag(f.name), str(getattr(geometry, f.name))]
    if policies:
        argv += ["--policies", ",".join(policies)]
    # CPU platform, forced host devices, and this repo's ``src`` on
    # PYTHONPATH so the child resolves ``repro`` regardless of cwd
    env = child_env(geometry.n_devices)
    src = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.measure.run", *argv],
        env=env, capture_output=True, text=True, timeout=timeout)
    if r.returncode != 0:
        raise RuntimeError(
            f"measurement subprocess for {arch!r} failed "
            f"(rc={r.returncode}):\n{r.stdout[-2000:]}\n{r.stderr[-2000:]}")
    return json.loads(
        (Path(out_dir) / f"{arch}.json").read_text())


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="repro.measure",
        description="Measure a real jax train step into a sweepable "
                    "jax: workload trace.")
    p.add_argument("--arch", required=True, choices=MEASURABLE_ARCHS)
    p.add_argument("--out-dir", default=None,
                   help="measurement directory (default: "
                        "$REPRO_MEASURE_DIR or results/measure/)")
    # geometry flags default to None so "explicitly passed" is
    # distinguishable from "follow the preset" (--smoke or full)
    full, smoke, chip = Geometry(), SMOKE_GEOMETRY, CHIP_GEOMETRY
    for f in dataclasses.fields(Geometry):
        p.add_argument(_geometry_flag(f.name), type=int, default=None,
                       dest=f.name,
                       help=f"default {getattr(full, f.name)} on CPU, "
                            f"{getattr(chip, f.name) or 'published/all'} on "
                            f"TPU (--smoke: {getattr(smoke, f.name)})")
    p.add_argument("--policies", default=None,
                   help="comma-separated sync policies "
                        "(default: at_end,wfbp,bucketed)")
    p.add_argument("--smoke", action="store_true",
                   help="tiny CI-sized geometry preset (individual "
                        "geometry flags still win)")
    return p


def _geometry_from_args(args: argparse.Namespace,
                        on_tpu: bool = False) -> Geometry:
    base = SMOKE_GEOMETRY if args.smoke else (
        CHIP_GEOMETRY if on_tpu else Geometry())
    return dataclasses.replace(base, **{
        f.name: getattr(args, f.name) for f in dataclasses.fields(Geometry)
        if getattr(args, f.name) is not None})


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    # forced host devices must be in XLA_FLAGS before jax's backend
    # starts; they are CPU devices, which a chip host leaves unused
    force_host_device_count(args.n_devices or Geometry().n_devices)
    import jax

    from repro.launch.compile_cache import enable_compilation_cache

    enable_compilation_cache()
    geometry = _geometry_from_args(
        args, on_tpu=jax.default_backend() == "tpu")
    out_dir = args.out_dir or default_out_dir()
    policies = tuple(t.strip() for t in args.policies.split(",")
                     if t.strip()) if args.policies else None
    doc = run_measurement(args.arch, out_dir, geometry, policies)
    brief = {k: doc[k] for k in
             ("workload", "device", "trace_path", "policy_times_s",
              "policy_losses", "t_update_s", "allreduce_fit",
              "bytes_crosscheck", "elapsed_s")}
    print(json.dumps(brief, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
