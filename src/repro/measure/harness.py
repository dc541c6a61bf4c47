"""Per-layer timing harness over the repo's *real* jax train steps.

Runs the explicit data-parallel S-SGD step (:mod:`repro.comm.ddp`)
under each gradient-sync policy on the visible devices — the chips of
a TPU host, or forced host CPU devices for rehearsals — and harvests
everything the DAG model needs:

* **whole-step wall time per policy** — the "measured" side of the
  paper's Fig. 4 comparison;
* **per-layer forward/backward seconds**, segmented via the layer-scan
  structure: the transformer executes ``num_units`` trips of one scan
  body over stacked parameters, so timing the jitted loss (forward)
  and its gradient (forward+backward) at two scan depths and fitting a
  line gives the per-trip (per-layer) cost as the slope and the
  non-scanned remainder (embedding + head + loss) as the intercept —
  measuring the *actual compiled scan body*, not a re-implementation;
* **per-payload collective times** on the same device mesh (one
  ``psum`` per distinct gradient payload), which both fill the trace's
  Comm. column and feed the alpha-beta fit in
  :mod:`repro.measure.calibrate`;
* **optimizer-update time** (``t_u``) and **HLO collective bytes** per
  policy (via :mod:`repro.launch.hlo`, while-loop-scaled) for the
  bytes cross-check.

The result is emitted as a paper-format
:class:`~repro.traces.format.Trace` (§VI), so measured runs round-trip
through the exact machinery the published traces use, and the ``jax:``
workload provider serves them to the sweep engine.

Requires ``n_devices`` visible devices: the chips of the host, or on
a CPU host forced host devices — :mod:`repro.measure.run` sets
:func:`repro.launch.hostdev.force_host_device_count` before the first
jax import.
"""
from __future__ import annotations

import dataclasses
import time
from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.ddp import make_ddp_train_step
from repro.comm.sync import DEFAULT_BUCKET_BYTES
from repro.launch import hlo as hlo_mod
from repro.launch.mesh import make_dp_mesh
from repro.measure.calibrate import device_cluster_name, grad_payload_bytes
from repro.models import transformer as T
from repro.models.common import ModelConfig
from repro.optim.sgd import sgd
from repro.traces.format import LayerRecord, Trace

#: The executable gradient-sync policies the harness measures (the
#: "none" policy is the single-device baseline, not a sync schedule).
MEASURED_SYNC_POLICIES = ("at_end", "wfbp", "bucketed")


# ----------------------------------------------------------------------
# Timing primitives
# ----------------------------------------------------------------------
def _timeit(fn: Callable, repeats: int) -> float:
    """Minimum wall seconds of ``fn()`` after one warmup call; ``fn``
    must block on its own result (callers wrap with
    ``jax.block_until_ready``).  Minimum, not median: wall-clock noise
    on a shared host is strictly additive, so the smallest observation
    is the least-contaminated estimate — which matters for the
    segmentation slopes, where noise comparable to one scan trip would
    otherwise leak into the per-layer costs."""
    fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times)


# ----------------------------------------------------------------------
# Scan-structure segmentation (pure math, unit-tested)
# ----------------------------------------------------------------------
@dataclass(frozen=True)
class SegmentTiming:
    """Per-layer costs segmented out of the scan: seconds per scanned
    unit (slope) and for the non-scanned remainder (intercept)."""

    unit_fwd_s: float
    unit_bwd_s: float
    rest_fwd_s: float
    rest_bwd_s: float


def segment_from_depths(units: Sequence[int], fwd_s: Sequence[float],
                        full_s: Sequence[float]) -> SegmentTiming:
    """Least-squares segmentation: ``fwd_s[i]`` (forward-only) and
    ``full_s[i]`` (forward+backward) are measured wall seconds at scan
    depth ``units[i]``.  The fitted slope is the per-unit cost, the
    intercept the non-scanned remainder; backward = full − forward.
    Negative values (timing noise on near-zero terms) clamp to 0.
    """
    if len(units) < 2:
        raise ValueError("need at least two scan depths to segment")
    u = np.asarray(units, dtype=np.float64)
    if len(set(units)) < 2:
        raise ValueError("scan depths must be distinct")
    f_slope, f_icpt = np.polyfit(u, np.asarray(fwd_s, dtype=np.float64), 1)
    t_slope, t_icpt = np.polyfit(u, np.asarray(full_s, dtype=np.float64), 1)
    unit_fwd = max(float(f_slope), 0.0)
    rest_fwd = max(float(f_icpt), 0.0)
    return SegmentTiming(
        unit_fwd_s=unit_fwd,
        unit_bwd_s=max(float(t_slope) - unit_fwd, 0.0),
        rest_fwd_s=rest_fwd,
        rest_bwd_s=max(float(t_icpt) - rest_fwd, 0.0),
    )


def _depth_variant(cfg: ModelConfig, n_units: int) -> ModelConfig:
    """Same family at a different scan depth: ``n_units`` pattern trips
    with the remainder-block count preserved, everything else equal."""
    rem = cfg.num_layers % len(cfg.layer_pattern)
    return dataclasses.replace(
        cfg, name=f"{cfg.name}-u{n_units}",
        num_layers=n_units * len(cfg.layer_pattern) + rem)


def _default_depths(cfg: ModelConfig) -> tuple[int, int]:
    u = cfg.num_units
    if u < 1:
        raise ValueError(
            f"{cfg.name}: segmentation needs at least one scanned unit "
            f"(num_layers {cfg.num_layers} < pattern "
            f"{cfg.layer_pattern!r})")
    # a 2x depth spread keeps the fitted slope well above wall-clock
    # noise even for tiny smoke models (one extra trip would not); the
    # deeper point is the measured model itself, so segmentation never
    # builds a model larger than the one the step trains
    return (u // 2, u) if u >= 2 else (1, 2)


# ----------------------------------------------------------------------
# The measurement itself
# ----------------------------------------------------------------------
@dataclass
class MeasuredRun:
    """Everything one instrumented-execution run harvested."""

    arch: str
    config_name: str
    n_devices: int
    batch_per_gpu: int
    seq_len: int
    num_units: int
    depths: tuple[int, int]
    trace: Trace                          # per-layer fwd/bwd/comm + bytes
    segments: SegmentTiming
    policy_times: dict[str, float]        # measured wall s/iter per policy
    policy_losses: dict[str, float]       # loss after the timed steps
    collective_stats: dict[str, dict]     # per policy: HLO-harvested bytes
    t_update_s: float
    allreduce_samples: list[tuple[float, float]]   # (payload bytes, seconds)
    unit_grad_bytes: float
    rest_grad_bytes: float
    elapsed_s: float

    @property
    def total_grad_bytes(self) -> float:
        return self.rest_grad_bytes + self.num_units * self.unit_grad_bytes

    def summary(self) -> dict:
        """JSON-serializable record (everything but the trace body)."""
        return {
            "arch": self.arch,
            "config": self.config_name,
            "n_devices": self.n_devices,
            "batch_per_gpu": self.batch_per_gpu,
            "seq_len": self.seq_len,
            "num_units": self.num_units,
            "depths": list(self.depths),
            "policy_times_s": self.policy_times,
            "policy_losses": self.policy_losses,
            "collective_stats": self.collective_stats,
            "t_update_s": self.t_update_s,
            "allreduce_samples": [[b, t] for b, t in self.allreduce_samples],
            "unit_grad_bytes": self.unit_grad_bytes,
            "rest_grad_bytes": self.rest_grad_bytes,
            "total_grad_bytes": self.total_grad_bytes,
            "segments": dataclasses.asdict(self.segments),
            "elapsed_s": self.elapsed_s,
        }


def _time_segments(cfg: ModelConfig, depths: Sequence[int],
                   batch_per_gpu: int, seq_len: int,
                   repeats: int) -> SegmentTiming:
    """Jit the loss (forward) and its gradient (forward+backward) at
    each scan depth on one device, time them, and segment."""
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (batch_per_gpu, seq_len), 0,
                                cfg.vocab_size)
    labels = jax.random.randint(jax.random.PRNGKey(1),
                                (batch_per_gpu, seq_len), 0, cfg.vocab_size)
    fwd_s, full_s = [], []
    for u in depths:
        cfg_u = _depth_variant(cfg, u)
        params = T.init_lm(cfg_u, jax.random.PRNGKey(2))

        def loss(p, c=cfg_u):
            return T.loss_fn(c, p, tokens, labels)[0]

        fwd = jax.jit(loss)
        bwd = jax.jit(jax.value_and_grad(loss))
        fwd_s.append(_timeit(
            lambda: jax.block_until_ready(fwd(params)), repeats))
        full_s.append(_timeit(
            lambda: jax.block_until_ready(bwd(params)), repeats))
    return segment_from_depths(list(depths), fwd_s, full_s)


def _time_allreduce(mesh, nbytes: float, repeats: int) -> float:
    """Measured wall seconds of one data-parallel mean all-reduce of a
    ``nbytes``-per-rank f32 payload on ``mesh`` (0.0 on one device —
    no collective is issued, matching the model's ``n=1`` convention).
    """
    n_dev = mesh.devices.size
    if n_dev <= 1 or nbytes <= 0:
        return 0.0
    from jax.sharding import PartitionSpec as P

    n = max(int(nbytes) // 4, 1)
    arr = jnp.ones((n_dev, n), jnp.float32)
    fn = jax.jit(jax.shard_map(
        lambda x: jax.lax.pmean(x, "data"), mesh=mesh,
        in_specs=P("data"), out_specs=P("data"), check_vma=False))
    return _timeit(lambda: jax.block_until_ready(fn(arr)), repeats)


def _time_policy_step(cfg: ModelConfig, mesh, policy: str,
                      batch: dict, step_iters: int,
                      bucket_bytes: float) -> tuple[float, dict, float]:
    """(measured seconds/iteration, HLO collective stats, last loss)
    for one executable sync policy: AOT-compile the ddp step once, read
    its optimized HLO for the bytes harvest (and the count of Pallas
    kernel calls, ``stats["pallas_calls"]``), then run it
    ``step_iters`` times back-to-back (outputs re-fed, one trailing
    block) — the steady-pipeline timing of the paper's
    measurements."""
    opt = sgd(lr=1e-2, momentum=0.9)
    step = make_ddp_train_step(cfg, opt, mesh, sync_policy=policy,
                               bucket_bytes=bucket_bytes)
    params = T.init_lm(cfg, jax.random.PRNGKey(3))
    opt_state = opt.init(params)
    compiled = step.lower(params, opt_state, batch).compile()
    hlo = compiled.as_text()
    stats = hlo_mod.collective_stats(
        hlo, loop_trip_count=max(cfg.num_units, 1)).to_dict()
    stats["pallas_calls"] = hlo.count('custom_call_target="tpu_custom_call"')

    p, st, m = compiled(params, opt_state, batch)      # warmup
    jax.block_until_ready(m["loss"])
    t0 = time.perf_counter()
    for _ in range(step_iters):
        p, st, m = compiled(p, st, batch)
    jax.block_until_ready(m["loss"])
    return ((time.perf_counter() - t0) / step_iters, stats,
            float(m["loss"]))


def measure_model(cfg: ModelConfig, *, arch: str = "",
                  n_devices: int = 2, batch_per_gpu: int = 2,
                  seq_len: int = 32,
                  policies: Sequence[str] = MEASURED_SYNC_POLICIES,
                  depths: tuple[int, int] | None = None,
                  repeats: int = 3, step_iters: int = 5,
                  bucket_bytes: float = DEFAULT_BUCKET_BYTES) -> MeasuredRun:
    """Instrument ``cfg``'s train step end to end on the first
    ``n_devices`` visible devices and return the full
    :class:`MeasuredRun`.

    ``batch_per_gpu`` is the per-device batch (the global batch is
    ``batch_per_gpu * n_devices``); segmentation and collective timing
    run at the per-device view, exactly how the paper measured
    per-layer costs on one GPU of the cluster.
    """
    t_start = time.perf_counter()
    avail = len(jax.devices())
    if avail < n_devices:
        raise RuntimeError(
            f"need {n_devices} devices but jax sees {avail} "
            f"{jax.default_backend()} device(s); on a CPU host run "
            f"`python -m repro.measure` (or call "
            f"repro.launch.hostdev.force_host_device_count before the "
            f"first jax import)")
    mesh = make_dp_mesh(n_devices)
    depths = depths or _default_depths(cfg)

    B = batch_per_gpu * n_devices
    key = jax.random.PRNGKey(0)
    batch = {
        "tokens": jax.random.randint(key, (B, seq_len), 0, cfg.vocab_size),
        "labels": jax.random.randint(jax.random.PRNGKey(1), (B, seq_len),
                                     0, cfg.vocab_size),
    }

    # 1) whole-step wall time + HLO collective bytes, per policy
    policy_times: dict[str, float] = {}
    policy_losses: dict[str, float] = {}
    collective: dict[str, dict] = {}
    for pol in policies:
        policy_times[pol], collective[pol], policy_losses[pol] = \
            _time_policy_step(cfg, mesh, pol, batch, step_iters,
                              bucket_bytes)

    # 2) per-layer segmentation via the scan structure (one device)
    segments = _time_segments(cfg, depths, batch_per_gpu, seq_len, repeats)

    # 3) gradient payloads + measured collectives per distinct payload
    unit_bytes, rest_bytes = grad_payload_bytes(cfg)
    total_bytes = rest_bytes + cfg.num_units * unit_bytes
    samples: list[tuple[float, float]] = []
    comm_of: dict[float, float] = {}
    for nbytes in sorted({unit_bytes, rest_bytes, total_bytes}):
        t = _time_allreduce(mesh, nbytes, repeats)
        comm_of[nbytes] = t
        if nbytes > 0 and t > 0:
            samples.append((nbytes, t))

    # 4) optimizer update (t_u); params and state are donated and
    # re-fed, so a full-width model holds one copy of each on the chip
    opt = sgd(lr=1e-2, momentum=0.9)
    params = T.init_lm(cfg, jax.random.PRNGKey(2))
    cur = {"p": params, "s": opt.init(params)}
    g = jax.tree_util.tree_map(jnp.ones_like, params)
    del params
    upd = jax.jit(opt.update, donate_argnums=(1, 2))

    def update_once():
        cur["p"], cur["s"] = upd(g, cur["s"], cur["p"])
        jax.block_until_ready(cur["p"])

    t_update = _timeit(update_once, repeats)
    del cur, g

    # 5) paper-format trace: the non-scanned remainder (embedding +
    # head + loss) as layer 0, one record per scanned unit.  Layer 0's
    # gradients genuinely release last in backward (the embedding), so
    # WFBP ordering is preserved; times in microseconds, per §VI.
    us = 1e6
    recs = [LayerRecord(0, "embed_head", segments.rest_fwd_s * us,
                        segments.rest_bwd_s * us,
                        comm_of.get(rest_bytes, 0.0) * us, rest_bytes)]
    for i in range(cfg.num_units):
        recs.append(LayerRecord(i + 1, f"unit{i}",
                                segments.unit_fwd_s * us,
                                segments.unit_bwd_s * us,
                                comm_of.get(unit_bytes, 0.0) * us,
                                unit_bytes))
    trace = Trace(
        network=cfg.name,
        cluster=device_cluster_name(mesh.devices.flat),
        iterations=(tuple(recs),),
        batch_per_gpu=batch_per_gpu,
        # int32 tokens + labels per sample position
        bytes_per_sample=8.0 * seq_len,
    )

    return MeasuredRun(
        arch=arch or cfg.name,
        config_name=cfg.name,
        n_devices=n_devices,
        batch_per_gpu=batch_per_gpu,
        seq_len=seq_len,
        num_units=cfg.num_units,
        depths=tuple(depths),
        trace=trace,
        segments=segments,
        policy_times=policy_times,
        policy_losses=policy_losses,
        collective_stats=collective,
        t_update_s=t_update,
        allreduce_samples=samples,
        unit_grad_bytes=unit_bytes,
        rest_grad_bytes=rest_bytes,
        elapsed_s=time.perf_counter() - t_start,
    )
