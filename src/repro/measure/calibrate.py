"""Calibration: cross-check and fit the harvested measurements.

Three jobs, all pure accounting over what the harness measured:

* **payload accounting** — gradient all-reduce bytes straight from the
  parameter pytree's shapes (:func:`grad_payload_bytes`), the ground
  truth both the ``jax:`` workload table's ``grad_bytes`` and the HLO
  harvest must agree with;
* **bytes cross-check** — the lowered step's while-loop-scaled HLO
  collective bytes (:mod:`repro.launch.hlo`) against the payload
  accounting, per sync policy (:func:`crosscheck_collective_bytes`).
  Catches drift in any of :mod:`repro.comm.sync`,
  :mod:`repro.launch.hlo` and :mod:`repro.core.workloads`;
* **alpha-beta fit** — measured ``(payload bytes, seconds)``
  all-reduce samples → a latency/bandwidth collective model
  (:func:`fit_alpha_beta`), from which :func:`comm_scale_from_fit`
  builds the ``comm_scale`` the DAG builder uses to cost fused
  gradient buckets.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import transformer as T
from repro.models.common import ModelConfig


def device_cluster_name(devices) -> str:
    """Cluster label of a measured trace: the devices' kind as jax
    reports it, slugged, and their count — ``tpu-v5-lite-x4`` on four
    TPU v5e chips, ``cpu-x2`` on two forced host devices."""
    devices = list(devices)
    kind = "-".join(devices[0].device_kind.lower().split())
    return f"{kind}-x{len(devices)}"

#: Bytes of the one metrics collective the ddp step issues besides the
#: gradient sync: ``pmean`` of the f32[2] vector ``(total_loss, loss)``.
METRIC_COLLECTIVE_BYTES = 8.0


def grad_payload_bytes(cfg: ModelConfig) -> tuple[float, float]:
    """``(per_unit_bytes, rest_bytes)``: gradient all-reduce payload of
    one scanned unit and of the non-scanned leaves, in the parameter
    dtype — from the parameter pytree's shapes, no allocation."""
    pshape = jax.eval_shape(lambda k: T.init_lm(cfg, k),
                            jax.random.PRNGKey(0))
    unit_bytes = 0.0
    rest_bytes = 0.0
    for path, leaf in jax.tree_util.tree_leaves_with_path(pshape):
        nbytes = float(leaf.size) * jnp.dtype(leaf.dtype).itemsize
        if path and getattr(path[0], "key", None) == "units":
            unit_bytes += nbytes / max(cfg.num_units, 1)
        else:
            rest_bytes += nbytes
    return unit_bytes, rest_bytes


def expected_collective_bytes(cfg: ModelConfig, sync_policy: str) -> float:
    """Bytes one iteration of the lowered ddp step *should* move
    through collectives under ``sync_policy``:

    * ``at_end`` / ``wfbp`` — every parameter's gradient once, in its
      own dtype (one fused pmean vs. layer-wise psums — same total
      payload, different placement);
    * ``bucketed`` — the same gradients upcast to flat **f32** buckets
      (:func:`repro.comm.sync.bucketed_pmean` concatenates in f32), so
      bytes are counted per leaf at 4 bytes/element — parameter trees
      mix dtypes (bf16 weights, f32 norms), so rescaling a
      dtype-weighted total would miscount;

    plus the f32[2] metrics pmean every policy issues.
    """
    if sync_policy not in ("at_end", "wfbp", "bucketed"):
        raise ValueError(f"unknown sync policy {sync_policy!r}")
    pshape = jax.eval_shape(lambda k: T.init_lm(cfg, k),
                            jax.random.PRNGKey(0))
    total = 0.0
    for leaf in jax.tree_util.tree_leaves(pshape):
        itemsize = 4.0 if sync_policy == "bucketed" \
            else float(jnp.dtype(leaf.dtype).itemsize)
        total += float(leaf.size) * itemsize
    return total + METRIC_COLLECTIVE_BYTES


@dataclass(frozen=True)
class BytesCrossCheck:
    """One policy's HLO-harvested collective bytes vs. the payload
    accounting (relative error on the HLO side)."""

    policy: str
    hlo_bytes: float
    expected_bytes: float

    @property
    def rel_err(self) -> float:
        if self.expected_bytes == 0:
            return 0.0 if self.hlo_bytes == 0 else float("inf")
        return abs(self.hlo_bytes - self.expected_bytes) / self.expected_bytes


def crosscheck_collective_bytes(cfg: ModelConfig,
                                collective_stats: dict[str, dict],
                                ) -> dict[str, BytesCrossCheck]:
    """Cross-check each measured policy's HLO collective bytes (the
    ``collective_stats`` of a :class:`~repro.measure.harness.
    MeasuredRun`) against :func:`expected_collective_bytes`."""
    return {
        pol: BytesCrossCheck(
            policy=pol,
            hlo_bytes=float(stats["total_bytes"]),
            expected_bytes=expected_collective_bytes(cfg, pol))
        for pol, stats in collective_stats.items()
    }


def fit_alpha_beta(samples: Sequence[tuple[float, float]],
                   ) -> tuple[float, float]:
    """Least-squares alpha-beta fit ``t = alpha + nbytes / beta`` over
    measured ``(payload bytes, seconds)`` all-reduce samples.

    Returns ``(latency_s, bandwidth_bytes_per_s)``.  Repeated samples
    of the same payload collapse to their minimum first (wall-clock
    noise is additive, so the smallest observation is the cleanest —
    the harness's own timing convention).  Degenerate inputs degrade
    gracefully: a single distinct payload pins latency to 0 and takes
    its bandwidth; no samples (single device — no collectives) return
    ``(0, inf)`` so the derived comm cost is exactly 0; a non-positive
    fitted slope (noise) also yields infinite bandwidth, and a negative
    intercept clamps to 0.
    """
    best: dict[float, float] = {}
    for b, t in samples:
        b, t = float(b), float(t)
        if b > 0 and t > 0:
            best[b] = min(t, best.get(b, t))
    if not best:
        return 0.0, float("inf")
    if len(best) == 1:
        (b, t), = best.items()
        return 0.0, b / t
    xs = np.array(sorted(best))
    ys = np.array([best[b] for b in xs])
    slope, icpt = np.polyfit(xs, ys, 1)
    bandwidth = 1.0 / slope if slope > 0 else float("inf")
    return max(float(icpt), 0.0), float(bandwidth)


def comm_scale_from_fit(latency_s: float, bandwidth_bytes_per_s: float,
                        ) -> Callable[[float, float], float]:
    """A ``comm_scale(total_bytes, naive_time) -> seconds`` closure for
    the DAG builder / simulator, from a measured alpha-beta fit — the
    measured counterpart of :func:`repro.core.costmodel.comm_scale_fn`.
    """

    def scale(total_bytes: float, _naive_time: float) -> float:
        if total_bytes <= 0:
            return 0.0
        return latency_s + total_bytes / bandwidth_bytes_per_s

    return scale
