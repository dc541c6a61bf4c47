"""Instrumented execution: measure this repo's *real* jax train steps
and feed them back into the DAG model (paper §V-D / §VI, closed).

The paper's validation loop is measure → trace → DAG → predict →
compare-with-measurement; its companion framework study shows the
per-layer costs must come from instrumented execution, not FLOP
counts.  This package is that loop for the repo's own executable
stack:

* :mod:`repro.measure.harness` — run :func:`repro.comm.ddp` train
  steps under each gradient-sync policy on forced host devices,
  segment per-layer forward/backward seconds out of the layer-scan
  structure, time collectives and the optimizer update, and emit a
  paper-format :class:`~repro.traces.format.Trace`;
* :mod:`repro.measure.calibrate` — cross-check harvested collective
  bytes against the HLO analysis (:mod:`repro.launch.hlo`) and the
  workload table's ``grad_bytes``, and fit an alpha-beta collective
  model to the measured all-reduces;
* :mod:`repro.measure.run` — the CLI runner
  (``python -m repro.measure --arch <id>``): measures in process on a
  chip host's chips, or on forced host CPU devices (shared helper
  :mod:`repro.launch.hostdev`), and writes ``<arch>.trace`` +
  ``<arch>.json`` into the measurement directory, from which the
  ``jax:`` workload provider (:mod:`repro.core.workloads`) serves
  sweepable tables.

``benchmarks/bench_model_vs_measured.py`` closes the Fig.-4 circle:
model-predicted vs measured iteration time per sync policy, gated in
CI.
"""
from repro.measure.calibrate import (BytesCrossCheck,
                                     comm_scale_from_fit,
                                     crosscheck_collective_bytes,
                                     device_cluster_name,
                                     expected_collective_bytes,
                                     fit_alpha_beta, grad_payload_bytes)
from repro.measure.harness import (MEASURED_SYNC_POLICIES, MeasuredRun,
                                   measure_model, segment_from_depths)

__all__ = [
    "MEASURED_SYNC_POLICIES", "MeasuredRun", "measure_model",
    "segment_from_depths", "grad_payload_bytes", "fit_alpha_beta",
    "comm_scale_from_fit", "expected_collective_bytes",
    "crosscheck_collective_bytes", "BytesCrossCheck", "device_cluster_name",
]
