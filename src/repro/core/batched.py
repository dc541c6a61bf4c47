"""Scenario-axis batched fast path: the whole sweep as matrices.

The per-scenario fast path (:func:`repro.core.sweep._fast_eval`) is
vectorized over the *layer* dimension only — every scenario still pays
a Python round-trip through ``resolve_workload -> iteration_costs ->
closed_form``, which caps the engine at roughly 10k scenarios/s.  This
module vectorizes the *scenario* axis too, in two tiers:

* **Kernel grid**: every per-layer cost (``t_f``/``t_b``/``t_c``), the
  pipeline terms and the WFBP residual depend only on ``(workload,
  cluster x interconnect, n_workers, collective, batch)`` — *not* on
  the overlap policy.  The unique points of that reduced product are
  evaluated as ``(K, L)`` matrices built in one shot from array-valued
  collective models (:mod:`repro.core.hardware`) over per-point
  ``(n_workers, bandwidth, latency)`` vectors, with the prefix-max
  formulation of the WFBP residual
  (:func:`repro.core.analytical.non_overlapped_comm_batch`) reducing
  them to ``(K,)`` terms — pure NumPy over both axes, no per-scenario
  Python.  Workloads of different depths share one zero-padded
  ``(…, L_max)`` table: a padded layer has ``t_f = t_b = t_c =
  grad_bytes = 0``, contributes nothing to any sum, and is masked out
  of the prefix-max.
* **Policy select**: Eqs. (2)/(3)/(5) and their late-H2D variants are
  ``max``/``+`` combinations of those ``(K,)`` terms; each scenario
  gathers its kernel point and selects its policy's equation — cheap
  ``(S,)`` vector ops, so adding policies to a grid costs almost
  nothing.

Schedule-dependent policies (bucket fusion, priority comm) ride the
same two tiers: the kernel additionally reduces padded ``(S, B)``
bucket matrices (structure from :mod:`repro.core.bucketsim`, fused
payloads costed through the same collective dispatch as the per-layer
``t_c``) to one timeline-residual column per distinct bucket size, and
the policy select substitutes that residual for the WFBP term — see
:func:`repro.core.analytical.has_timeline_form` for why this is exact.

Correctness contract: every closed-form row agrees with the
per-scenario reference implementation ``_fast_eval`` to <= 1e-9
relative, and every timeline row with the event-driven
``simulate_steady`` oracle to <= 1e-6 (property-tested on the default,
mixed and frontier grids).  This module is the throughput engine
:func:`repro.core.sweep.sweep` routes every batched-eligible scenario
through.

Both front ends — :class:`GridEvaluator` for grids,
:func:`scenario_points` for scenario lists — build one
:class:`KernelPoints`, the axis tables and per-point codes every kernel
takes.  :func:`grid_evaluator` memoizes the prepared *structure* of a
grid (kernel points, label lists and, once built, the jax backend's
evaluator) keyed by grid value and resolved table identity — numeric
results are recomputed on every :meth:`GridEvaluator.run`, never
cached.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Sequence

import numpy as np

from repro.core import analytical, bucketsim, obs
from repro.core import het as het_mod
from repro.core.hardware import (CLUSTERS, alltoall_coeffs,
                                 apply_interconnect_preset,
                                 hierarchical_allreduce_coeffs,
                                 ring_allreduce_coeffs,
                                 tree_allreduce_coeffs)
from repro.core.policies import Policy, get_policy
from repro.core.resulttable import METHOD_LABELS, rows_from_table
from repro.core.scenarios import (Scenario, ScenarioGrid,
                                  normalize_interconnect,
                                  normalize_sync_k)
from repro.core.workloads import WorkloadTable, resolve_workload

_COLLECTIVE_CODE = {"ring": 0, "tree": 1, "hierarchical": 2}

#: Kernel points evaluated per ``(K, L)`` matrix allocation — bounds
#: transient memory on huge grids without measurably hurting speed.
KERNEL_CHUNK = 8192


# ----------------------------------------------------------------------
# Axis tables: everything a code vector indexes into.
# ----------------------------------------------------------------------
@dataclass
class _WorkloadAxis:
    """Unique workloads of the batch, padded to a shared layer count.

    Analytic tables populate ``flops``; measured ones populate
    ``tf_meas``/``tb_meas`` (the other family's rows are zero, so the
    combined expression ``flops*batch/rate + tf_meas*scale`` is exact
    for both — adding literal 0.0 is FP-identity).
    """

    names: list[str]                  # row-label spelling, as given
    flops: np.ndarray                 # (W, Lmax) per-sample fwd flops
    tf_meas: np.ndarray               # (W, Lmax) measured fwd s @ batch_default
    tb_meas: np.ndarray               # (W, Lmax) measured bwd s @ batch_default
    grad_bytes: np.ndarray            # (W, Lmax) all-reduce payload
    routed: np.ndarray                # (W, Lmax) routed experts' part of it
    a2a: np.ndarray                   # (W, Lmax) one all-to-all, bytes/sample
    bwd_ratio: np.ndarray             # (W,)
    batch_default: np.ndarray         # (W,) float64
    bytes_per_sample: np.ndarray      # (W,)
    param_bytes: np.ndarray           # (W,)
    t_io_meas: np.ndarray             # (W,) measured input-pipeline s (0 if analytic)
    has_meas_io: np.ndarray           # (W,) bool
    batch_locked: np.ndarray          # (W,) bool
    table_names: list[str]            # canonical names, for error messages
    any_measured: bool                # any table with measured t_f/t_b
    any_meas_io: bool                 # any table with measured t_io


def _workload_axis(names: Sequence[str]) -> _WorkloadAxis:
    """Resolve + pad the unique workloads of a batch."""
    tables: list[WorkloadTable] = [resolve_workload(n) for n in names]
    lmax = max((t.num_layers for t in tables), default=1)
    W = len(tables)
    flops = np.zeros((W, lmax))
    tf_meas = np.zeros((W, lmax))
    tb_meas = np.zeros((W, lmax))
    grad = np.zeros((W, lmax))
    routed = np.zeros((W, lmax))
    a2a = np.zeros((W, lmax))
    for i, t in enumerate(tables):
        L = t.num_layers
        grad[i, :L] = t.grad_bytes
        routed[i, :L] = t.routed_bytes
        a2a[i, :L] = t.a2a_bytes_per_sample
        if t.is_measured:
            tf_meas[i, :L] = t.t_f
            tb_meas[i, :L] = t.t_b
        else:
            flops[i, :L] = t.flops_fwd
    return _WorkloadAxis(
        names=list(names),
        flops=flops, tf_meas=tf_meas, tb_meas=tb_meas, grad_bytes=grad,
        routed=routed, a2a=a2a,
        bwd_ratio=np.array([t.bwd_fwd_ratio for t in tables]),
        batch_default=np.array([t.batch_default for t in tables],
                               dtype=np.float64),
        bytes_per_sample=np.array([t.bytes_per_sample for t in tables]),
        param_bytes=np.array([t.param_bytes for t in tables]),
        t_io_meas=np.array([t.t_io_measured or 0.0 for t in tables]),
        has_meas_io=np.array([t.t_io_measured is not None for t in tables],
                             dtype=bool),
        batch_locked=np.array([t.batch_locked for t in tables], dtype=bool),
        table_names=[t.name for t in tables],
        # distinct flags: a trace can carry measured t_f/t_b without a
        # 'data' layer (no measured t_io) — gating the compute-time
        # terms on measured *I/O* would silently zero its layers
        any_measured=any(t.is_measured for t in tables),
        any_meas_io=any(t.t_io_measured is not None for t in tables))


def _check_batch_locked(wax: _WorkloadAxis, widx: np.ndarray,
                        batch: np.ndarray) -> None:
    """Exactly the guard
    :meth:`~repro.core.workloads.WorkloadTable.iteration_costs` applies
    per scenario: a batch override on a trace without a recorded batch
    is an error (its measured times cannot be rescaled)."""
    bad = wax.batch_locked[widx] & (batch > 0) \
        & (batch != wax.batch_default[widx])
    if bool(bad.any()):
        i = int(np.argmax(bad))
        raise ValueError(
            f"workload {wax.table_names[int(widx[i])]!r} has no recorded "
            f"batch size (no '# batch:' header in the trace), so its "
            f"measured times cannot be rescaled to batch_per_gpu="
            f"{int(batch[i])}; leave batch_per_gpu unset")


@dataclass
class _ClusterAxis:
    """Unique ``(cluster, interconnect)`` pairs, resolved once.

    Node sizing (``with_workers``) never changes any of these
    parameters, so the pair — not the worker count — is the right
    resolution key.
    """

    intra_bw: np.ndarray
    intra_lat: np.ndarray
    inter_bw: np.ndarray
    inter_lat: np.ndarray
    gpn: np.ndarray                   # gpus_per_node, int64
    disk_lat: np.ndarray
    disk_bw: np.ndarray
    h2d_lat: np.ndarray
    h2d_bw: np.ndarray
    rate: np.ndarray                  # achieved flop/s
    hbm_bw: np.ndarray


def _cluster_axis(pairs: Sequence[tuple[str, str | None]]) -> _ClusterAxis:
    specs = [apply_interconnect_preset(CLUSTERS[c], ic) for c, ic in pairs]
    return _ClusterAxis(
        intra_bw=np.array([c.intra.effective_bandwidth for c in specs]),
        intra_lat=np.array([c.intra.latency for c in specs]),
        inter_bw=np.array([c.inter.effective_bandwidth for c in specs]),
        inter_lat=np.array([c.inter.latency for c in specs]),
        gpn=np.array([c.gpus_per_node for c in specs], dtype=np.int64),
        disk_lat=np.array([c.disk.latency for c in specs]),
        disk_bw=np.array([c.disk.effective_bandwidth for c in specs]),
        h2d_lat=np.array([c.h2d.latency for c in specs]),
        h2d_bw=np.array([c.h2d.effective_bandwidth for c in specs]),
        rate=np.array([c.device.peak_flops * c.device.compute_efficiency
                       for c in specs]),
        hbm_bw=np.array([c.device.hbm_bandwidth for c in specs]))


@dataclass
class _EPAxis:
    """Expert-parallel tables, built only for a batch with some ``ep >
    1``: one row per ``(workload, ep)`` *pair* (the kernel point's
    ``we`` code), plus the per-workload all-to-all suffix tables.

    A pair splits each layer's gradient into a dense payload, all-reduced
    over ``n`` ranks, and an expert payload ``routed / ep``, all-reduced
    over the ``n / ep`` ranks of an expert group.  An ``ep = 1`` pair is
    the whole gradient as dense and no expert payload, so its columns
    are the tables the kernel reads without expert parallelism and its
    points come out bit for bit as they do there.  ``d*``/``e*`` are
    inclusive prefix sums over forward layer order of the payloads and
    of the live-collective counts; ``a2a_suf``/``a2a_sufc`` inclusive
    suffix sums of all-to-all bytes per sample and of all-to-all layers
    (column 0 holds the totals); ``buckets`` one entry per timeline
    spec: release layer, mask and the suffix sums over issue order of
    each bucket's dense and expert bytes and live collectives, buckets
    closed on the per-device payload ``dense + expert``."""

    dcum: np.ndarray                  # (P, L)
    dcnt: np.ndarray
    ecum: np.ndarray
    ecnt: np.ndarray
    param_bytes: np.ndarray           # (P,) per-device parameter bytes
    a2a_suf: np.ndarray               # (W, L)
    a2a_sufc: np.ndarray
    buckets: list                     # per spec: (release, mask, sd, sdc, se, sec)


def _suffix(a: np.ndarray) -> np.ndarray:
    """Inclusive suffix sums along the last axis."""
    return np.flip(np.cumsum(np.flip(a, -1), -1), -1)


def _ep_axis(wax: _WorkloadAxis, pair_w: np.ndarray, pair_ep: np.ndarray,
             tl_specs: Sequence[tuple[float, bool]]) -> _EPAxis:
    """The :class:`_EPAxis` of the ``(workload index, ep)`` pairs."""
    with obs.span("sweep.build.ep"):
        one = (pair_ep == 1)[:, None]
        grad, routed = wax.grad_bytes[pair_w], wax.routed[pair_w]
        dense = np.where(one, grad, grad - routed)
        expert = np.where(one, 0.0, routed / pair_ep[:, None])
        rsum = wax.routed.sum(axis=1)[pair_w]
        param = np.where(pair_ep == 1, wax.param_bytes[pair_w],
                         wax.param_bytes[pair_w] - rsum + rsum / pair_ep)
        buckets = []
        for bb, _ in tl_specs:
            rows = [bucketsim.bucket_partition(g > 0, d + e, bb)
                    for g, d, e in zip(grad, dense, expert)]
            B = max((len(r) for r in rows), default=0) or 1
            release = np.zeros((len(rows), B), dtype=np.int64)
            sums = np.zeros((4, len(rows), B))
            for i, r in enumerate(rows):
                for j, members in enumerate(r):
                    release[i, j] = members[-1]
                    db = sum(dense[i, m] for m in members)
                    eb = sum(expert[i, m] for m in members)
                    sums[:, i, j] = (db, db > 0, eb, eb > 0)
            mask = np.zeros((len(rows), B))
            for i, r in enumerate(rows):
                mask[i, :len(r)] = 1.0
            buckets.append((release, mask, *(_suffix(x) for x in sums)))
        return _EPAxis(
            dcum=np.cumsum(dense, axis=1),
            dcnt=np.cumsum((dense > 0).astype(np.float64), axis=1),
            ecum=np.cumsum(expert, axis=1),
            ecnt=np.cumsum((expert > 0).astype(np.float64), axis=1),
            param_bytes=param,
            a2a_suf=_suffix(wax.a2a),
            a2a_sufc=_suffix((wax.a2a > 0).astype(np.float64)),
            buckets=buckets)


@dataclass
class _PolicyAxis:
    names: list[str]
    overlap_io: np.ndarray            # (P,) bool
    overlap_comm: np.ndarray
    h2d_early: np.ndarray
    has_fast: np.ndarray              # (P,) exact per-layer closed form
    has_tl: np.ndarray                # (P,) exact bucket-timeline form
    tier: np.ndarray                  # (P,) METHOD_LABELS index
    tl_spec: np.ndarray               # (P,) index into tl_specs, -1 = none
    #: Unique ``(bucket_bytes, overlap_comm)`` pairs the kernel must
    #: compute a timeline-residual column for.  Priority-only policies
    #: (no buckets) need no column: order-independence makes their
    #: residual the per-layer WFBP term ``tc_no`` already on hand.
    tl_specs: list[tuple[float, bool]]


def _policy_axis(names: Sequence[str]) -> _PolicyAxis:
    pols: list[Policy] = [get_policy(n) for n in names]
    specs: dict[tuple[float, bool], int] = {}
    tl_spec = np.full(len(pols), -1, dtype=np.int64)
    for i, p in enumerate(pols):
        if analytical.has_timeline_form(p) and p.bucket_bytes:
            key = (float(p.bucket_bytes), bool(p.overlap_comm))
            tl_spec[i] = specs.setdefault(key, len(specs))
    has_fast = np.array([analytical.has_closed_form(p) for p in pols],
                        dtype=bool)
    has_tl = np.array([analytical.has_timeline_form(p) for p in pols],
                      dtype=bool)
    return _PolicyAxis(
        names=list(names),
        overlap_io=np.array([p.overlap_io for p in pols], dtype=bool),
        overlap_comm=np.array([p.overlap_comm for p in pols], dtype=bool),
        h2d_early=np.array([p.h2d_early for p in pols], dtype=bool),
        has_fast=has_fast,
        has_tl=has_tl,
        tier=np.where(has_fast, 0, np.where(has_tl, 1, 2)).astype(np.int64),
        tl_spec=tl_spec,
        tl_specs=list(specs))


def _het_multipliers(wtab: dict[str, np.ndarray], hk: np.ndarray,
                     synck: np.ndarray):
    """``(tmul, bwmul, latmul)``: the slowest-worker multipliers of the
    points whose padded worker-table rows are ``hk``
    (:func:`repro.core.analytical.worker_bottleneck`), the compute one
    the ``synck``-th order statistic of the per-worker rates where some
    point has a partial-sync threshold."""
    tm, bm, lm = analytical.worker_bottleneck(
        wtab["inv_speed"], wtab["bw_mult"], wtab["lat_mult"])
    if bool((synck != 0).any()):
        nrow = wtab["n"][hk]
        tmul = analytical.kth_order_statistic(
            wtab["inv_speed"][hk], nrow,
            analytical.effective_sync_k(synck, nrow))
    else:
        tmul = tm[hk]
    return tmul, bm[hk], lm[hk]


@dataclass
class KernelPoints:
    """The kernel points of a batch, as both front ends build them
    (:class:`GridEvaluator` ``.points``, :func:`scenario_points`) and
    every kernel takes them: the shared axis tables, and one entry per
    point of each code vector.

    ``w``/``c`` index ``wax``/``cax``; ``hk`` is the point's row of the
    padded worker table ``wtab`` (:func:`repro.core.het.worker_table_rows`)
    and ``synck`` its normalized sync threshold (``0`` = full sync).
    ``tmul``/``bwmul``/``latmul`` are the slowest-worker multipliers, all
    ``None`` on a homogeneous batch, so the kernel's fast path stays
    literally untouched.  ``epx`` and each point's ``(workload, ep)``
    pair row ``we`` and group size ``ep`` are ``None`` on a batch
    without ``ep > 1``.  A new mechanism adds its codes here, filled by
    both front ends."""

    wax: _WorkloadAxis
    cax: _ClusterAxis
    pax: _PolicyAxis
    wtab: dict[str, np.ndarray]
    epx: _EPAxis | None
    w: np.ndarray
    c: np.ndarray
    coll: np.ndarray
    n: np.ndarray
    batch: np.ndarray
    hk: np.ndarray
    synck: np.ndarray
    tmul: np.ndarray | None
    bwmul: np.ndarray | None
    latmul: np.ndarray | None
    we: np.ndarray | None
    ep: np.ndarray | None

    def take(self, idx) -> "KernelPoints":
        """The points ``idx`` (an index array or a slice): every code
        vector gathered, the tables shared, ``None`` codes kept."""
        return dataclasses.replace(self, **{
            f: getattr(self, f)[idx] for f in _POINT_CODES
            if getattr(self, f) is not None})


#: The per-point fields of :class:`KernelPoints`.
_POINT_CODES = ("w", "c", "coll", "n", "batch", "hk", "synck", "tmul",
                "bwmul", "latmul", "we", "ep")


# ----------------------------------------------------------------------
# Tier 1: the affine kernel — policy-independent cost terms.
# ----------------------------------------------------------------------
def _derated_links(cax: _ClusterAxis, cidx: np.ndarray,
                   bwmul: np.ndarray | None, latmul: np.ndarray | None):
    """``(intra_bw, intra_lat, inter_bw, inter_lat)`` per point, with
    the slowest-worker multipliers applied where given."""
    intra_bw, intra_lat = cax.intra_bw[cidx], cax.intra_lat[cidx]
    inter_bw, inter_lat = cax.inter_bw[cidx], cax.inter_lat[cidx]
    if bwmul is not None:
        intra_bw = intra_bw * bwmul
        inter_bw = inter_bw * bwmul
    if latmul is not None:
        intra_lat = intra_lat * latmul
        inter_lat = inter_lat * latmul
    return intra_bw, intra_lat, inter_bw, inter_lat


def _alltoall_point_coeffs(cax: _ClusterAxis, cidx: np.ndarray,
                           ep: np.ndarray, bwmul: np.ndarray | None,
                           latmul: np.ndarray | None):
    """Per-point ``(per_byte, per_message)`` of one all-to-all over an
    EP group of ``ep`` contiguous ranks: on the intra-node link while
    the group fits in a node, else the inter-node one, derated like the
    all-reduce links."""
    intra_bw, intra_lat, inter_bw, inter_lat = _derated_links(
        cax, cidx, bwmul, latmul)
    use_intra = ep <= cax.gpn[cidx]
    return alltoall_coeffs(ep, np.where(use_intra, intra_bw, inter_bw),
                           np.where(use_intra, intra_lat, inter_lat))


def _collective_coeffs(cax: _ClusterAxis, cidx: np.ndarray,
                       coll: np.ndarray, n: np.ndarray,
                       bwmul: np.ndarray | None = None,
                       latmul: np.ndarray | None = None,
                       gpn: np.ndarray | None = None,
                       ) -> tuple[np.ndarray, np.ndarray]:
    """Per-point affine collective coefficients ``(per_byte,
    per_message)``: every collective model is affine in the payload for
    fixed ``(n, links)`` (see :mod:`repro.core.hardware`), and each
    algorithm's coefficients are evaluated only on its own points (the
    collective axis partitions the kernel grid).

    ``bwmul``/``latmul`` are per-point slowest-worker link multipliers
    (per-worker vectors already reduced by
    :func:`repro.core.analytical.worker_bottleneck`): a heterogeneous
    collective is gated by its slowest link, so both the intra- and
    inter-node parameters are derated before the algorithm dispatch
    (hierarchical scales both levels).  ``None`` (or all-ones — FP
    multiply by 1.0 is exact) leaves the homogeneous path bit-identical.

    ``gpn`` overrides the devices a node holds per point: an expert
    group of ``n / ep`` strided ranks holds ``max(1, gpn // ep)`` a
    node (:meth:`repro.core.hardware.ClusterSpec.expert_group`).
    """
    n_f = n.astype(np.float64)
    intra_bw, intra_lat, inter_bw, inter_lat = _derated_links(
        cax, cidx, bwmul, latmul)
    if gpn is None:
        gpn = cax.gpn[cidx]
    use_intra = n <= gpn
    link_bw = np.where(use_intra, intra_bw, inter_bw)
    link_lat = np.where(use_intra, intra_lat, inter_lat)
    codes_present = np.unique(coll)
    if len(codes_present) == 1:
        sels: list = [slice(None)]
    else:
        sels = [np.nonzero(coll == code)[0] for code in codes_present]
    per_byte = np.empty(len(cidx))
    per_message = np.empty(len(cidx))
    for code, sel in zip(codes_present, sels):
        if code == 0:
            a, b = ring_allreduce_coeffs(n_f[sel], link_bw[sel],
                                         link_lat[sel])
        elif code == 1:
            a, b = tree_allreduce_coeffs(n[sel], link_bw[sel],
                                         link_lat[sel])
        else:
            a, b = hierarchical_allreduce_coeffs(
                n[sel], gpn[sel], intra_bw[sel], intra_lat[sel],
                inter_bw[sel], inter_lat[sel])
        per_byte[sel], per_message[sel] = a, b
    return per_byte, per_message


def _compute_row_map(points: KernelPoints):
    """``(uw, uc, ubatch, ut, uk)``: the unique *compute rows* of
    ``points`` and the point -> row map.  ``t_f``/``t_b`` (and
    everything derived from them: prefix/suffix sums, ``comp``) depend
    only on ``(workload, device rate, batch)`` — on a product grid that
    is a tiny set (workloads x devices, not x interconnects x workers x
    collectives), so the layer-axis matrices are built on ``U`` rows
    and gathered per point instead of being recomputed ``K`` times.

    ``tmul`` (per-point slowest-worker compute multipliers) joins the
    unique key — it must, because it scales the *measured* time tables
    too, which bypass the device rate — and comes back as the
    per-unique-row ``ut`` column (``None`` when not given).  A constant
    ``tmul`` contributes one key level and leaves the row set (and the
    homogeneous path) unchanged."""
    widx, cidx, batch, tmul = points.w, points.c, points.batch, points.tmul
    urate, rinv = np.unique(points.cax.rate[cidx], return_inverse=True)
    ubv, binv = np.unique(batch, return_inverse=True)
    key = (widx * len(ubv) + binv) * len(urate) + rinv
    if tmul is not None:
        utm, tinv = np.unique(tmul, return_inverse=True)
        key = key * len(utm) + tinv
    _, rep, uk = np.unique(key, return_index=True, return_inverse=True)
    ut = None if tmul is None else tmul[rep]
    return widx[rep], cidx[rep], batch[rep], ut, uk


def _kernel_cols(points: KernelPoints,
                 chunk: int = KERNEL_CHUNK) -> dict[str, np.ndarray]:
    """Policy-independent terms for every kernel point, reduced over
    the layer axis: ``(K,)`` vectors of ``io_h2d``, ``t_h2d``, ``comp``
    (= sum t_f + sum t_b), ``sum_c``, ``tc_no``, ``t_u``, plus the
    resolved ``n_f``/``batch_f``.

    The evaluation is **cumsum-free over the point axis**: per-point
    collective costs are affine in the payload (``per_byte * M +
    per_message``, :func:`_collective_coeffs`), so every per-layer
    prefix sum collapses to the workload-level cumulative tables
    ``cumgrad``/``cumcount`` scaled by two per-point scalars, and
    ``sum_c`` to ``per_byte * sum(grad) + per_message * n_comm``.  The
    backward-time tables themselves are built once per unique
    ``(workload, rate, batch)`` *compute row* (:func:`_compute_row_map`
    — a handful of rows even on frontier-sized grids) and gathered per
    point.  The surviving ``(k, L)`` work is a fused multiply-add +
    masked max for the WFBP residual, built ``chunk`` points at a time
    so huge grids stay in bounded memory.

    ``points.pax.tl_specs`` (:attr:`_PolicyAxis.tl_specs`) adds one
    bucket-timeline residual column ``tl<i>`` per unique
    ``(bucket_bytes, overlap_comm)`` pair, through the same affine
    collapse: bucket structure from the shared
    :func:`repro.core.bucketsim.bucket_table` boundaries, duration
    suffix sums from :func:`repro.core.bucketsim.suffix_tables` (so
    fused buckets amortize latency exactly as
    ``repro.core.costmodel.comm_scale_fn`` does), release times
    gathered from the per-row backward suffix — the exact
    :func:`repro.core.bucketsim.timeline_residual` makespan, never
    materializing a per-point duration matrix.

    ``points.tmul``/``bwmul``/``latmul`` (all ``(K,)`` or ``None``) are the
    slowest-worker bottleneck multipliers of the heterogeneity engine —
    per-worker vectors already reduced by
    :func:`repro.core.analytical.worker_bottleneck` (and, on the Monte
    Carlo straggler path, already folded with each draw's jitter):
    ``tmul`` scales every compute-time term (analytic *and* measured —
    it joins the unique-row key via :func:`_compute_row_map`), while
    ``bwmul``/``latmul`` derate the collective links
    (:func:`_collective_coeffs`).  ``t_io``/``t_h2d`` stay homogeneous
    (their channels are per-worker and identical) and ``t_u`` is
    HBM-bandwidth-bound, not compute-rate-bound, so neither is scaled.
    All-ones multipliers are bit-identity (IEEE ``x * 1.0 == x``).

    Expert parallelism (``points.epx`` with the per-point pair codes
    ``we`` and group sizes ``ep``, all ``None`` for a batch without it) adds
    two more affine pairs per point.  The all-to-alls: four a layer, at
    ``alltoall_coeffs`` over the EP group times ``batch`` times the
    layer's bytes per sample, two of them in the backward pass — so
    ``a_byte * a2a_suf + a_msg * a2a_sufc`` joins the backward suffix
    (hence every release time and the WFBP candidates), the backward
    total and, twice, ``comp``; they are not compute and ``tmul`` does
    not scale them.  The expert all-reduce: the expert group's
    coefficients times the pair's ``ecum``/``ecnt`` beside the dense
    ``dcum``/``dcnt``, in ``sum_c``, the WFBP candidates and the bucket
    durations; ``t_u`` reads the pair's per-device parameter bytes.
    """
    wax, cax, epx = points.wax, points.cax, points.epx
    tl_specs = points.pax.tl_specs
    K = len(points.w)
    # Per-workload layer tables: inclusive payload/count prefix sums
    # (forward order) for the affine WFBP residual, plus the bucket
    # structure + suffix tables per timeline spec — all O(W x L), built
    # once per call, gathered per chunk.
    grad = wax.grad_bytes
    comm_mask = (grad > 0).astype(np.float64)
    cumgrad = np.cumsum(grad, axis=1)
    cumcount = np.cumsum(comm_mask, axis=1)
    gradsum, ncomm = cumgrad[:, -1], cumcount[:, -1]
    btables = []
    for bb, _ in tl_specs:
        bt = bucketsim.bucket_table(wax.grad_bytes, bb)
        btables.append((bt,) + bucketsim.suffix_tables(bt))
    out = {name: np.empty(K) for name in
           ("io_h2d", "t_h2d", "comp", "sum_c", "tc_no", "t_u",
            "n_f", "batch_f")}
    for i in range(len(tl_specs)):
        out[f"tl{i}"] = np.empty(K)
    for lo in range(0, K, chunk):
        sl = slice(lo, lo + chunk)
        p = points.take(sl)
        w, c, nn, cl = p.w, p.c, p.n, p.coll
        batch_f = np.where(p.batch > 0, p.batch,
                           wax.batch_default[w]).astype(np.float64)
        n_f = nn.astype(np.float64)

        # compute costs: (U, L) on the unique compute rows only
        uw, uc, ub, ut, uk = _compute_row_map(p)
        ubatch_f = np.where(ub > 0, ub,
                            wax.batch_default[uw]).astype(np.float64)
        tfa = wax.flops[uw] * ubatch_f[:, None] / cax.rate[uc][:, None]
        t_f = tfa
        t_b = wax.bwd_ratio[uw][:, None] * tfa
        if wax.any_measured:          # adding literal 0.0 rows is exact,
            scale = (ubatch_f / wax.batch_default[uw])[:, None]
            t_f = t_f + wax.tf_meas[uw] * scale    # but skip it when the
            t_b = t_b + wax.tb_meas[uw] * scale    # batch has no traces
        if ut is not None:            # slowest-worker compute multiplier
            t_f = t_f * ut[:, None]
            t_b = t_b * ut[:, None]
        prefix_b = np.cumsum(t_b, axis=1)
        total_b_u = prefix_b[:, -1]
        suffix_b_u = (total_b_u[:, None] - prefix_b) + t_b   # inclusive
        comp_u = t_f.sum(axis=1) + t_b.sum(axis=1)
        total_b = total_b_u[uk]

        # per-point affine collective coefficients
        bm, lm = p.bwmul, p.latmul
        per_byte, per_message = _collective_coeffs(cax, c, cl, nn, bm, lm)
        if epx is not None:
            pe, ep_k = p.we, p.ep
            e_byte, e_msg = _collective_coeffs(
                cax, c, cl, nn // ep_k, bm, lm,
                gpn=np.maximum(cax.gpn[c] // ep_k, 1))
            a_byte, a_msg = _alltoall_point_coeffs(cax, c, ep_k, bm, lm)
            a_byte = 2.0 * a_byte * batch_f     # two a layer, each pass
            a_msg = 2.0 * a_msg
            suffix_b = suffix_b_u[uk] \
                + a_byte[:, None] * epx.a2a_suf[w] \
                + a_msg[:, None] * epx.a2a_sufc[w]
            a2a_pass = a_byte * epx.a2a_suf[w, 0] \
                + a_msg * epx.a2a_sufc[w, 0]
            total_b = total_b + a2a_pass

        # pipeline terms: (k,)
        nbytes_in = batch_f * wax.bytes_per_sample[w]
        t_io = cax.disk_lat[c] + nbytes_in / cax.disk_bw[c]
        if wax.any_meas_io:
            t_io = np.where(wax.has_meas_io[w],
                            wax.t_io_meas[w] * batch_f
                            / wax.batch_default[w],
                            t_io)
        t_h2d = cax.h2d_lat[c] + nbytes_in / cax.h2d_bw[c]

        out["io_h2d"][sl] = t_io + t_h2d
        out["t_h2d"][sl] = t_h2d
        # WFBP residual (non_overlapped_comm_batch, affine form): the
        # comm prefix sum at layer l is per_byte*cumgrad[l] +
        # per_message*cumcount[l]; candidates masked to comm layers
        # (t_c > 0 <=> grad > 0 when n > 1; when n <= 1 both
        # coefficients are 0, every candidate is <= total_b and the
        # clamp yields the same exact 0.0)
        if epx is None:
            out["comp"][sl] = comp_u[uk]
            out["sum_c"][sl] = per_byte * gradsum[w] + per_message * ncomm[w]
            cand = suffix_b_u[uk]
            cand += per_byte[:, None] * cumgrad[w]
            cand += per_message[:, None] * cumcount[w]
            out["t_u"][sl] = 3.0 * wax.param_bytes[w] / cax.hbm_bw[c]
        else:
            out["comp"][sl] = comp_u[uk] + 2.0 * a2a_pass
            out["sum_c"][sl] = per_byte * epx.dcum[pe, -1] \
                + per_message * epx.dcnt[pe, -1] \
                + e_byte * epx.ecum[pe, -1] + e_msg * epx.ecnt[pe, -1]
            cand = suffix_b.copy()
            cand += per_byte[:, None] * epx.dcum[pe]
            cand += per_message[:, None] * epx.dcnt[pe]
            cand += e_byte[:, None] * epx.ecum[pe]
            cand += e_msg[:, None] * epx.ecnt[pe]
            out["t_u"][sl] = 3.0 * epx.param_bytes[pe] / cax.hbm_bw[c]
        cand *= comm_mask[w]
        out["tc_no"][sl] = np.maximum(
            cand.max(axis=1, initial=0.0) - total_b, 0.0)
        out["n_f"][sl] = n_f
        out["batch_f"][sl] = batch_f

        # bucket-timeline residuals: the timeline_residual makespan
        # with the duration suffix sum in affine form — release times
        # from the unique-row backward suffix, one fused multiply-add +
        # masked max over the (k, B) bucket axis per spec
        for i, ((bt, sufnb, sufcnt), (_, ov_comm)) in \
                enumerate(zip(btables, tl_specs)):
            if epx is not None:
                rel, bmask, sd, sdc, se, sec = epx.buckets[i]
                if ov_comm:
                    cand = np.take_along_axis(suffix_b, rel[pe], axis=1)
                else:
                    cand = np.repeat(total_b[:, None], rel.shape[1], axis=1)
                cand += per_byte[:, None] * sd[pe]
                cand += per_message[:, None] * sdc[pe]
                cand += e_byte[:, None] * se[pe]
                cand += e_msg[:, None] * sec[pe]
                cand *= bmask[pe]
                out[f"tl{i}"][sl] = np.maximum(
                    cand.max(axis=1, initial=0.0) - total_b, 0.0)
                continue
            if ov_comm:
                release_u = np.take_along_axis(
                    suffix_b_u, bt.release_layer[uw], axis=1)
            else:
                release_u = np.broadcast_to(
                    total_b_u[:, None], (len(uw), bt.n_buckets))
            cand = release_u[uk]
            cand += per_byte[:, None] * sufnb[w]
            cand += per_message[:, None] * sufcnt[w]
            cand *= bt.mask[w]
            out[f"tl{i}"][sl] = np.maximum(
                cand.max(axis=1, initial=0.0) - total_b, 0.0)
    return out


# ----------------------------------------------------------------------
# Tier 2: per-scenario policy select — cheap (S,) vector ops.
# ----------------------------------------------------------------------
def _policy_select(pax: _PolicyAxis, polidx: np.ndarray,
                   kc: dict[str, np.ndarray],
                   kidx: np.ndarray | None,
                   chain_extra: np.ndarray | None = None
                   ) -> dict[str, np.ndarray]:
    """Gather each scenario's kernel point (``kidx=None`` means the
    identity map) and select its policy's steady-state form — Eqs. (2),
    (3), (5) and the late-H2D variants for closed-form policies, the
    bucket-timeline residual for schedule-dependent ones — plus the
    zero-comm weak-scaling baseline with the *same* policy (what
    ``_fast_eval`` / ``_sim_eval`` compute for the speedup column).

    ``chain_extra`` is an additive extension of the GPU/update chain
    (the fault model's serialized checkpoint restores, which gate the
    update broadcast).  It sits *inside* the pipeline max, so an
    I/O-bound pipeline absorbs part of the penalty — exactly what the
    event-driven DAG produces.  The zero-comm baseline ``t1`` is
    unaffected (it is the hypothetical fault-free single-GPU time)."""
    def g(a: np.ndarray) -> np.ndarray:
        return a if kidx is None else a[kidx]

    io_h2d, t_h2d = g(kc["io_h2d"]), g(kc["t_h2d"])
    comp, sum_c = g(kc["comp"]), g(kc["sum_c"])
    tc_no, t_u = g(kc["tc_no"]), g(kc["t_u"])
    n_f, batch_f = g(kc["n_f"]), g(kc["batch_f"])

    ov_io = pax.overlap_io[polidx]
    ov_comm = pax.overlap_comm[polidx]
    early = pax.h2d_early[polidx]

    comm_term = np.where(ov_comm, tc_no, sum_c)     # WFBP residual or full
    # Schedule-dependent overrides.  Bucketed policies substitute their
    # bucket-timeline residual column; priority-only policies need no
    # override — the net channel is work-conserving, so reordering
    # never moves the last comm finish and the per-layer term already
    # selected (tc_no / sum_c) *is* their residual.
    spec_of = pax.tl_spec[polidx]
    for i in range(len(pax.tl_specs)):
        comm_term = np.where(spec_of == i, g(kc[f"tl{i}"]), comm_term)
    gpu_chain = comp + comm_term + t_u
    if chain_extra is not None:
        gpu_chain = gpu_chain + chain_extra
    eq2 = io_h2d + gpu_chain                        # no I/O overlap
    eq_early = np.maximum(io_h2d, gpu_chain)        # Eq. (3)/(5)
    eq_late = np.maximum(io_h2d, t_h2d + gpu_chain)  # late-H2D variants
    t_iter = np.where(~ov_io, eq2, np.where(early, eq_early, eq_late))

    base_chain = comp + t_u                         # zero-comm baseline
    t1 = np.where(~ov_io, io_h2d + base_chain,
                  np.where(early, np.maximum(io_h2d, base_chain),
                           np.maximum(io_h2d, t_h2d + base_chain)))

    # method tier code: index into resulttable.METHOD_LABELS (0 =
    # closed form, 1 = bucket timeline, 2 = simulator-only — the
    # caller discards tier-2 rows for the simulator fallback).  Kept
    # as an int column so the select stays label-free; the table
    # assembly gathers the labels.
    return {
        "batch": batch_f,
        "iteration_time_s": t_iter,
        "samples_per_sec": n_f * batch_f / t_iter,
        "speedup": n_f * t1 / t_iter,
        "t_comm_s": sum_c,
        "t_comp_s": comp,
        "method_code": pax.tier[polidx],
    }


def select_to_columns(cols: dict[str, np.ndarray],
                      labels: dict[str, np.ndarray]) -> dict[str, np.ndarray]:
    """Assemble a tidy columnar table (:data:`repro.core.resulttable.COLUMNS`
    order) from a :func:`_policy_select` output plus per-scenario label
    columns (object arrays, already gathered).  Shared by both batched
    backends — the NumPy grid/list front ends here and
    :class:`repro.core.batched_jax.JaxGridRun`.

    The tail columns ``t_mean_s``/``t_p95_s``/``t_p99_s`` come from the
    straggler Monte Carlo pass when present; deterministic rows (no
    straggler spec, or zero jitter) default to ``iteration_time_s`` —
    the distribution is a point mass there.  ``method`` is taken from
    ``labels`` where the caller gathered it already (the jax grid path,
    while its kernel runs), else gathered from ``cols["method_code"]``.
    """
    t_iter = np.asarray(cols["iteration_time_s"])
    return {
        "workload": labels["workload"],
        "cluster": labels["cluster"],
        "n_workers": labels["n_workers"],
        "policy": labels["policy"],
        "collective": labels["collective"],
        "interconnect": labels["interconnect"],
        "het": labels["het"],
        "straggler": labels["straggler"],
        "sync_k": labels["sync_k"],
        "faults": labels["faults"],
        "ep_size": labels["ep_size"],
        "batch_per_gpu": np.asarray(cols["batch"]).astype(np.int64),
        "iteration_time_s": t_iter,
        "samples_per_sec": np.asarray(cols["samples_per_sec"]),
        "speedup": np.asarray(cols["speedup"]),
        "t_comm_s": np.asarray(cols["t_comm_s"]),
        "t_comp_s": np.asarray(cols["t_comp_s"]),
        "t_mean_s": np.asarray(cols.get("t_mean_s", t_iter)),
        "t_p95_s": np.asarray(cols.get("t_p95_s", t_iter)),
        "t_p99_s": np.asarray(cols.get("t_p99_s", t_iter)),
        "method": (labels["method"] if "method" in labels
                   else METHOD_LABELS[np.asarray(cols["method_code"])]),
    }


# ----------------------------------------------------------------------
# Failure-model Monte Carlo: per-draw kernel evaluation, reduced to
# tails (straggler jitter, K-of-N sync, fault injection).
# ----------------------------------------------------------------------
def _apply_mc_tails(points: KernelPoints, codes: dict[str, np.ndarray],
                    specs: tuple[Sequence, Sequence],
                    cols: dict[str, np.ndarray], seed: int,
                    active: np.ndarray | None = None) -> None:
    """Attach ``t_mean_s``/``t_p95_s``/``t_p99_s`` to a
    :func:`_policy_select` output in place.

    Every input is per-*row*: ``points`` holds each row's kernel point
    (a front end's points gathered at the rows, :meth:`KernelPoints.take`)
    with its worker-table row ``hk``, its deterministic slowest-link
    multipliers and its sync threshold ``synck``; ``codes`` holds its
    policy ``pi``, its straggler spec ``sti`` and its fault spec ``fli``,
    indices into ``points.pax`` and into ``specs = (st_specs,
    ft_specs)`` (parsed :class:`repro.core.het.StragglerSpec` /
    :class:`repro.core.het.FaultSpec` or ``None``).  Deterministic rows
    (no stochastic spec) keep the point-mass default — tails equal to
    ``iteration_time_s``, bit-exact.

    Stochastic rows take a Monte Carlo pass: per draw ``d`` the
    bottleneck theorem applies with multiplier ``kth_w(J[d, w] /
    speed_w)`` — the K-th order statistic of the jitter folded with the
    het profile's per-worker rates (``K = n`` under full sync recovers
    the max; the slow worker and the unlucky worker need not coincide,
    and under K-of-N each draw elects its *own* K-th worker) — so each
    draw is one deterministic kernel evaluation at that ``tmul``.  A
    fault spec contributes a per-draw penalty ``restart * crashes[d]``
    (crash counts from
    :meth:`~repro.core.het.FaultSpec.crash_matrix`) injected into the
    GPU/update chain via ``_policy_select(chain_extra=...)``: restores
    serialize on the shared checkpoint store and gate the update
    broadcast, so they extend the chain *inside* the pipeline max — an
    I/O-bound pipeline absorbs part of the penalty, exactly as the
    event-driven DAG does.  Rows sharing
    ``(kernel point, policy, worker table, sync_k)`` are deduplicated
    first, per-point draw multipliers are built once per unique
    ``(worker-table row, sync_k)`` pair (the ``(D, W)`` matrices come
    from :meth:`~repro.core.het.StragglerSpec.draw_matrix`, keyed by
    ``(spec, n, seed)`` so every backend and shard consumes the
    identical sample; the draw count is the straggler spec's when one
    is present, else the fault spec's), and the expanded ``point x
    draw`` set streams through the ordinary two-tier kernel in blocks
    of roughly :data:`KERNEL_CHUNK` rows.  The per-draw iteration times
    reduce to mean/p95/p99 with ``np.quantile`` on the host — shared by
    the jax backend, which guarantees the draw-for-draw <= 1e-6
    agreement.

    ``active=False`` rows (simulator-fallback policies) are skipped:
    their whole row, tails included, is overwritten by the per-draw
    oracle path in :mod:`repro.core.sweep`.
    """
    t_iter = np.asarray(cols["iteration_time_s"])
    cols["t_mean_s"] = t_iter.copy()
    cols["t_p95_s"] = t_iter.copy()
    cols["t_p99_s"] = t_iter.copy()
    st_specs, ft_specs = specs
    pax, wtab, hks, synck = points.pax, points.wtab, points.hk, points.synck
    polidx, stidx, fidx = codes["pi"], codes["sti"], codes["fli"]
    for si, st in enumerate(st_specs):
        st_live = st is not None and not st.is_deterministic
        for fi, ft in enumerate(ft_specs):
            ft_live = ft is not None and not ft.is_deterministic
            if not (st_live or ft_live):
                continue
            sel = (stidx == si) & (fidx == fi)
            if active is not None:
                sel = sel & active
            rows = np.nonzero(sel)[0]
            if not len(rows):
                continue
            # one MC evaluation per unique (kernel point, policy,
            # worker table, sync_k) tuple — rows sharing all four see
            # identical draws
            key = np.stack([points.w[rows], points.c[rows],
                            points.coll[rows], points.n[rows],
                            points.batch[rows], polidx[rows], hks[rows],
                            synck[rows]]
                           + ([] if points.epx is None
                              else [points.we[rows]]), axis=1)
            _, rep, uinv = np.unique(key, axis=0, return_index=True,
                                     return_inverse=True)
            urows = rows[rep]
            U = len(urows)
            D = st.draws if st_live else ft.draws
            tmuls = np.empty((U, D))
            pens = np.zeros((U, D)) if ft_live else None
            hkpairs = np.stack([hks[urows], synck[urows]], axis=1)
            for h, k in np.unique(hkpairs, axis=0):
                pts = np.nonzero((hkpairs[:, 0] == h)
                                 & (hkpairs[:, 1] == k))[0]
                nw = int(wtab["n"][h])
                J = (st.draw_matrix(nw, seed) if st_live
                     else np.ones((D, nw)))
                times = J * wtab["inv_speed"][h, :nw]      # (D, nw)
                keff = nw if k == 0 else min(max(int(k), 1), nw)
                if keff >= nw:
                    tmuls[pts] = times.max(axis=1)
                else:
                    tmuls[pts] = np.partition(
                        times, keff - 1, axis=1)[:, keff - 1]
                if ft_live:
                    crashes = ft.crash_matrix(
                        nw, seed, draws=D).sum(axis=1)     # (D,)
                    pens[pts] = ft.restart * crashes
            mean_u = np.empty(U)
            p95_u = np.empty(U)
            p99_u = np.empty(U)
            blk = max(1, KERNEL_CHUNK // D)
            for lo in range(0, U, blk):
                pt = urows[lo:lo + blk]
                m = len(pt)
                rp = np.repeat(pt, D)
                kc = _kernel_cols(dataclasses.replace(
                    points.take(rp), tmul=tmuls[lo:lo + m].ravel()))
                ti = _policy_select(
                    pax, polidx[rp], kc, kidx=None,
                    chain_extra=None if pens is None
                    else pens[lo:lo + m].ravel())[
                    "iteration_time_s"].reshape(m, D)
                mean_u[lo:lo + m] = ti.mean(axis=1)
                p95_u[lo:lo + m] = np.quantile(ti, 0.95, axis=1)
                p99_u[lo:lo + m] = np.quantile(ti, 0.99, axis=1)
            cols["t_mean_s"][rows] = mean_u[uinv]
            cols["t_p95_s"][rows] = p95_u[uinv]
            cols["t_p99_s"][rows] = p99_u[uinv]


# ----------------------------------------------------------------------
# Grid front end: codes straight from the axes, no Scenario objects.
# ----------------------------------------------------------------------
def _axis_codes(sizes: Sequence[int]) -> list[np.ndarray]:
    """Flat cross-product code vectors, rightmost axis fastest — the
    exact :meth:`ScenarioGrid.expand` order."""
    out = []
    for i, size in enumerate(sizes):
        after = int(np.prod(sizes[i + 1:], dtype=np.int64))
        before = int(np.prod(sizes[:i], dtype=np.int64))
        out.append(np.tile(np.repeat(np.arange(size), after), before))
    return out


class GridEvaluator:
    """A :class:`ScenarioGrid` prepared for batched evaluation.

    Builds the axis tables, the kernel-grid code vectors (policy axis
    dropped), the scenario -> kernel-point map and the row label lists
    directly from the grid's cross-product structure — no per-scenario
    Python objects at all.  Closed-form *and* bucket-timeline policies
    are both batched; scenarios whose policy has neither form come
    back as ``None`` rows and :meth:`scenario_at` materializes just
    those for the simulator fallback.

    The evaluator holds only *structure*; :meth:`run` computes the
    numbers.  Get instances through :func:`grid_evaluator`, which
    memoizes them by grid value + workload-table identity.
    """

    def __init__(self, grid: ScenarioGrid):
        grid.validate_axes()
        self.grid = grid
        nW, nC = len(grid.workloads), len(grid.clusters)
        nK, nP = len(grid.worker_counts), len(grid.policies)
        nA, nI = len(grid.collectives), len(grid.interconnects)
        nH, nT = len(grid.het_profiles), len(grid.stragglers)
        nQ, nF = len(grid.sync_ks), len(grid.faults)
        nE = len(grid.ep_sizes)
        self._sizes = (nW, nC, nK, nE, nP, nA, nI, nH, nT, nQ, nF)
        self.n_scenarios = (nW * nC * nK * nE * nP * nA * nI * nH * nT
                            * nQ * nF)

        wax = _workload_axis(grid.workloads)
        pairs = [(c, ic) for c in grid.clusters for ic in grid.interconnects]
        pax = _policy_axis(grid.policies)

        # Kernel grid: the scenario product with the policy, straggler
        # and fault axes dropped — order (workloads, clusters, workers,
        # ep_sizes, collectives, interconnects, het_profiles, sync_ks),
        # rightmost fastest.  The straggler and fault axes never change a
        # deterministic kernel point (jitter and crash penalties only
        # enter the Monte Carlo pass); the het axis does, through the
        # bottleneck multipliers, and the sync_k axis does too — it
        # picks *which* order statistic of the per-worker rates gates
        # the iteration.  O(K) int vectors; every per-*scenario*
        # quantity is derived per chunk instead (see scenario_codes),
        # so preparation stays O(axes + K) however large the scenario
        # product is.
        kw, kc, kk, ke, ka, ki, kh, kq = _axis_codes(
            (nW, nC, nK, nE, nA, nI, nH, nQ))
        kbatch = np.full(len(kw), grid.batch_per_gpu or 0, dtype=np.int64)
        khk = kh * nK + kk                      # (het profile, n) pair row
        sk_values = np.array(
            [normalize_sync_k(k) for k in grid.sync_ks], dtype=np.int64)
        ksynck = sk_values[kq]                  # 0 = full sync
        _check_batch_locked(wax, kw, kbatch)

        # Expert parallelism: one table row per (workload, ep) pair,
        # built only when some point has ep > 1 (else the kernel runs
        # exactly as without the axis).
        self._ep_values = np.array([int(e) for e in grid.ep_sizes],
                                   dtype=np.int64)
        epx = _ep_axis(wax, np.repeat(np.arange(nW), nE),
                       np.tile(self._ep_values, nW), pax.tl_specs) \
            if bool((self._ep_values > 1).any()) else None

        # Heterogeneity: one padded per-worker table row per (profile,
        # n_workers) pair, reduced once to the bottleneck multipliers
        # and gathered per kernel point.  All-homogeneous grids keep
        # the multipliers as None so the kernel's fast path stays
        # literally untouched (not merely bit-identical) — exact even
        # under K-of-N sync, where every order statistic of an all-ones
        # rate vector is 1.0; a partial-sync threshold only changes the
        # *deterministic* kernel point when workers actually differ.
        profiles = [het_mod.parse_het_profile(h) for h in grid.het_profiles]
        wtab = het_mod.worker_table_rows(
            [(prof, int(n)) for prof in profiles
             for n in grid.worker_counts])
        tmul, bwmul, latmul = _het_multipliers(wtab, khk, ksynck) \
            if any(p is not None for p in profiles) else (None,) * 3
        self.points = KernelPoints(
            wax=wax, cax=_cluster_axis(pairs), pax=pax, wtab=wtab, epx=epx,
            w=kw, c=kc * nI + ki,               # (cluster, interconnect) pair
            coll=np.array([_COLLECTIVE_CODE[c] for c in grid.collectives],
                          dtype=np.int64)[ka],
            n=np.array([int(k) for k in grid.worker_counts],
                       dtype=np.int64)[kk],
            batch=kbatch, hk=khk, synck=ksynck,
            tmul=tmul, bwmul=bwmul, latmul=latmul,
            we=None if epx is None else kw * nE + ke,   # (workload, ep) row
            ep=None if epx is None else self._ep_values[ke])
        #: ``(st_specs, ft_specs)``: the parsed straggler and fault specs
        #: the scenario codes ``sti``/``fli`` index.
        self.specs = ([het_mod.parse_straggler(s) for s in grid.stragglers],
                      [het_mod.parse_fault(f) for f in grid.faults])
        self.any_mc = any(x is not None and not x.is_deterministic
                          for x in self.specs[0] + self.specs[1])
        #: The :class:`repro.core.batched_jax.JaxGridEvaluator` built on
        #: this structure once the jax backend has used it: it lives as
        #: long as this evaluator's structure-memo entry.
        self.jax = None

        per_policy = self.n_scenarios // nP if nP else 0
        self.n_fast = per_policy * int(pax.has_fast.sum())
        self.n_timeline = per_policy * int(pax.has_tl.sum())
        self.all_batched = \
            self.n_fast + self.n_timeline == self.n_scenarios

        # Per-axis label values (tiny object arrays, fancy-indexed per
        # chunk by the derived codes).
        self._wl_values = np.array(list(grid.workloads), dtype=object)
        self._cl_values = np.array(list(grid.clusters), dtype=object)
        self._n_values = np.array([int(k) for k in grid.worker_counts],
                                  dtype=np.int64)
        self._pol_values = np.array(list(grid.policies), dtype=object)
        self._coll_values = np.array(list(grid.collectives), dtype=object)
        self._ic_values = np.array(
            [normalize_interconnect(ic) for ic in grid.interconnects],
            dtype=object)
        self._ht_values = np.array(
            [het_mod.normalize_het(h) for h in grid.het_profiles],
            dtype=object)
        self._st_values = np.array(
            [het_mod.normalize_straggler(s) for s in grid.stragglers],
            dtype=object)
        self._sk_values = sk_values
        self._fl_values = np.array(
            [het_mod.normalize_fault(f) for f in grid.faults],
            dtype=object)

    def __len__(self) -> int:
        return self.n_scenarios

    def scenario_codes(self, lo: int, hi: int) -> dict[str, np.ndarray]:
        """Axis codes, the kernel-point map and the fast mask for flat
        scenario indices ``[lo, hi)``, derived arithmetically from the
        expand() order (rightmost axis fastest) — O(chunk) work and
        memory, nothing per-scenario is ever stored."""
        nW, nC, nK, nE, nP, nA, nI, nH, nT, nQ, nF = self._sizes
        r = np.arange(lo, hi, dtype=np.int64)
        fli = r % nF
        r //= nF
        ski = r % nQ
        r //= nQ
        sti = r % nT
        r //= nT
        hp = r % nH
        r //= nH
        ii = r % nI
        r //= nI
        ai = r % nA
        r //= nA
        pi = r % nP
        r //= nP
        ei = r % nE
        r //= nE
        ki = r % nK
        r //= nK
        ci = r % nC
        wi = r // nC
        kidx = (((((((wi * nC + ci) * nK + ki) * nE + ei) * nA + ai) * nI
                  + ii) * nH + hp) * nQ + ski)
        return {"wi": wi, "ci": ci, "ki": ki, "ei": ei, "pi": pi, "ai": ai,
                "ii": ii, "hi": hp, "sti": sti, "ski": ski, "fli": fli,
                "kidx": kidx,
                "batched": (self.points.pax.has_fast[pi]
                            | self.points.pax.has_tl[pi])}

    def label_columns(self, codes: dict[str, np.ndarray]) -> dict:
        return {
            "workload": self._wl_values[codes["wi"]],
            "cluster": self._cl_values[codes["ci"]],
            "n_workers": self._n_values[codes["ki"]],
            "policy": self._pol_values[codes["pi"]],
            "collective": self._coll_values[codes["ai"]],
            "interconnect": self._ic_values[codes["ii"]],
            "het": self._ht_values[codes["hi"]],
            "straggler": self._st_values[codes["sti"]],
            "sync_k": self._sk_values[codes["ski"]],
            "faults": self._fl_values[codes["fli"]],
            "ep_size": self._ep_values[codes["ei"]],
        }

    def _apply_tails(self, codes: dict[str, np.ndarray],
                     cols: dict[str, np.ndarray], seed: int) -> None:
        """Attach the tail columns for the rows of ``codes`` in place:
        the point-mass default everywhere, overwritten by the straggler
        Monte Carlo pass (:func:`_apply_mc_tails`) on stochastic rows.
        Simulator-fallback rows are excluded — their tails come from
        the per-draw oracle in :mod:`repro.core.sweep`."""
        if not self.any_mc:
            t_iter = np.asarray(cols["iteration_time_s"])
            cols["t_mean_s"] = t_iter
            cols["t_p95_s"] = t_iter
            cols["t_p99_s"] = t_iter
            return
        _apply_mc_tails(self.points.take(codes["kidx"]), codes, self.specs,
                        cols, seed, active=codes["batched"])

    def run(self, seed: int = 0) -> "GridRun":
        """Evaluate the kernel grid (fresh numbers every call) and
        return the per-run table materializer.  ``seed`` keys the
        straggler Monte Carlo draws (ignored on deterministic grids)."""
        return GridRun(self, _kernel_cols(self.points), seed=seed)

    def run_span(self, lo: int, hi: int, seed: int = 0):
        """Evaluate just the flat scenario indices ``[lo, hi)`` —
        kernel restricted to the unique kernel points the span touches,
        so a worker evaluating one shard never pays for the whole grid.
        Returns ``(table, batched)``: the columnar result table and the
        per-row batched mask (``False`` rows carry tier-2 placeholder
        numbers the caller must overwrite with the simulator — see
        :mod:`repro.core.parallel`)."""
        codes = self.scenario_codes(lo, hi)
        uk, inv = np.unique(codes["kidx"], return_inverse=True)
        kc = _kernel_cols(self.points.take(uk))
        cols = _policy_select(self.points.pax, codes["pi"], kc, inv)
        self._apply_tails(codes, cols, seed)
        return (select_to_columns(cols, self.label_columns(codes)),
                codes["batched"])

    def scenario_at(self, i: int) -> Scenario:
        """Materialize flat index ``i`` (used for simulator-fallback
        entries only)."""
        return self.grid.scenario_at(i)


class GridRun:
    """One evaluation of a grid: the ``(K,)`` kernel columns plus the
    shared structure, materializing columnar result tables chunk by
    chunk (:meth:`table_slice` is the hot path; :meth:`rows_slice` is
    the per-row compat view)."""

    def __init__(self, ev: GridEvaluator, kernel_cols: dict[str, np.ndarray],
                 seed: int = 0):
        self._ev = ev
        self._kc = kernel_cols
        self._seed = seed

    def __len__(self) -> int:
        return self._ev.n_scenarios

    def columns_slice(self, lo: int, hi: int) -> dict[str, np.ndarray]:
        """Numeric result columns (plus ``method`` labels as a Python
        list) for flat scenario indices ``[lo, hi)`` — the
        policy-selected values before tidy-table assembly.  The
        kernel-only surface the throughput benchmark times and the jax
        backend's differential gate compares against."""
        ev = self._ev
        codes = ev.scenario_codes(lo, hi)
        cols = _policy_select(ev.points.pax, codes["pi"], self._kc,
                              codes["kidx"])
        ev._apply_tails(codes, cols, self._seed)
        cols["method"] = METHOD_LABELS[cols.pop("method_code")].tolist()
        return cols

    def table_slice(self, lo: int, hi: int):
        """Columnar result table for flat scenario indices ``[lo, hi)``
        in grid order — label columns gathered from the per-axis value
        arrays, numeric columns straight from the policy select.
        Returns ``(table, batched)`` where ``batched`` is the per-row
        mask; ``False`` rows carry tier-2 placeholder numbers (their
        policy needs the simulator) that the caller overwrites via
        :func:`repro.core.resulttable.fill_rows`."""
        ev = self._ev
        codes = ev.scenario_codes(lo, hi)
        cols = _policy_select(ev.points.pax, codes["pi"], self._kc,
                              codes["kidx"])
        ev._apply_tails(codes, cols, self._seed)
        return (select_to_columns(cols, ev.label_columns(codes)),
                codes["batched"])

    def rows_slice(self, lo: int, hi: int) -> list[dict | None]:
        """Batched rows for flat scenario indices ``[lo, hi)`` in grid
        order; entries whose policy needs the simulator come back as
        ``None`` for the caller to fill."""
        table, batched = self.table_slice(lo, hi)
        rows: list[dict | None] = rows_from_table(table)
        if not self._ev.all_batched:
            for i in np.nonzero(~batched)[0].tolist():
                rows[i] = None                # selected a bogus equation
        return rows


#: Structure memo: prepared evaluators keyed by grid value + the
#: identity of the resolved workload tables (holding the tables alive
#: keeps the ids stable; a re-resolved table — e.g. an on-disk trace
#: whose mtime changed — misses the memo and rebuilds).  One memo for
#: both backends: the jax backend's evaluator lives in its entry's
#: :attr:`GridEvaluator.jax`.
_STRUCTURE_MEMO: dict = {}
_MEMO_LIMIT = 64


def _memo_key(grid: ScenarioGrid):
    """``(key, tables)``: ``grid``'s structure-memo key and the resolved
    workload tables it names; ``key`` is ``None`` where the grid is not
    hashable (e.g. list-valued axes)."""
    try:
        tables = tuple(resolve_workload(w) for w in grid.workloads)
        key = (grid, tuple(id(t) for t in tables))
        hash(key)
    except TypeError:
        return None, ()
    return key, tables


def grid_evaluator(grid: ScenarioGrid) -> GridEvaluator:
    """Memoized :class:`GridEvaluator` for ``grid`` (falls back to a
    fresh instance when the grid isn't hashable)."""
    key, tables = _memo_key(grid)
    if key is None:
        return GridEvaluator(grid)
    hit = _STRUCTURE_MEMO.get(key)
    if hit is not None:
        return hit[0]
    if len(_STRUCTURE_MEMO) >= _MEMO_LIMIT:
        _STRUCTURE_MEMO.clear()
    ev = GridEvaluator(grid)
    _STRUCTURE_MEMO[key] = (ev, tables)
    return ev


def cached_evaluator(grid: ScenarioGrid) -> GridEvaluator | None:
    """The memoized :class:`GridEvaluator` of ``grid``, or ``None`` —
    a pure probe (nothing is built, no entry is added): how the jax
    backend finds its evaluator, and how the sweep service
    (:mod:`repro.core.service`) accounts cache hit/miss rates without
    perturbing the cache it is measuring."""
    try:
        key, _ = _memo_key(grid)
    except ValueError:
        return None
    hit = None if key is None else _STRUCTURE_MEMO.get(key)
    return None if hit is None else hit[0]


# ----------------------------------------------------------------------
# Scenario-list front end (arbitrary iterables, already validated).
# ----------------------------------------------------------------------
def scenario_points(scenarios: Sequence[Scenario]):
    """One Python pass over a scenario list: ``(points, codes, specs)``,
    the list front end's :attr:`GridEvaluator.points`,
    :meth:`GridEvaluator.scenario_codes` (``pi``/``sti``/``fli``) and
    :attr:`GridEvaluator.specs`, with the identity scenario ->
    kernel-point map.  The axes are the list's unique workloads,
    ``(cluster, interconnect)`` pairs, policies, ``(het, n_workers)``
    worker-table rows, straggler and fault specs and, where some
    scenario has ``ep_size > 1``, ``(workload, ep)`` pairs.  Shared by
    :func:`eval_scenarios_table` and the jax backend's list front end
    (:func:`repro.core.batched_jax.eval_scenarios_table_jax`), so both
    backends agree on structure; raises ``ValueError`` if any
    scenario's policy has neither a closed nor a bucket-timeline form.
    """
    keys: dict[str, dict] = {k: {} for k in ("w", "c", "p", "h", "s", "f")}

    def code(axis: str, value) -> int:
        d = keys[axis]
        i = d.get(value)
        if i is None:
            i = d[value] = len(d)
        return i

    S = len(scenarios)
    widx, cidx, polidx, coll, n, batch, hks, stidx, fidx, synck, ep = (
        np.empty(S, dtype=np.int64) for _ in range(11))
    for i, s in enumerate(scenarios):
        widx[i] = code("w", s.workload)
        cidx[i] = code("c", (s.cluster, s.interconnect))
        polidx[i] = code("p", s.policy)
        coll[i] = _COLLECTIVE_CODE[s.collective]
        n[i] = s.n_workers
        batch[i] = s.batch_per_gpu or 0
        hks[i] = code("h", (het_mod.normalize_het(s.het), int(s.n_workers)))
        stidx[i] = code("s", het_mod.normalize_straggler(s.straggler))
        fidx[i] = code("f", het_mod.normalize_fault(s.faults))
        synck[i] = normalize_sync_k(s.sync_k)
        ep[i] = s.ep_size
    wax = _workload_axis(list(keys["w"]))
    _check_batch_locked(wax, widx, batch)
    pax = _policy_axis(list(keys["p"]))
    batched_ok = pax.has_fast | pax.has_tl
    if not bool(batched_ok[polidx].all()):
        bad = [pax.names[int(p)]
               for p in np.unique(polidx[~batched_ok[polidx]])]
        raise ValueError(f"policies with neither a closed form nor a "
                         f"bucket-timeline form cannot take the batched "
                         f"path: {bad}")
    wtab = het_mod.worker_table_rows(
        [(het_mod.parse_het_profile(h), k) for h, k in keys["h"]])
    tmul, bwmul, latmul = _het_multipliers(wtab, hks, synck) \
        if any(h != "none" for h, _ in keys["h"]) else (None,) * 3
    epx = we = None
    if bool((ep > 1).any()):
        pairs, we = np.unique(np.stack([widx, ep], axis=1), axis=0,
                              return_inverse=True)
        epx = _ep_axis(wax, pairs[:, 0], pairs[:, 1], pax.tl_specs)
        we = we.reshape(-1)
    points = KernelPoints(
        wax=wax, cax=_cluster_axis(list(keys["c"])), pax=pax, wtab=wtab,
        epx=epx, w=widx, c=cidx, coll=coll, n=n, batch=batch, hk=hks,
        synck=synck, tmul=tmul, bwmul=bwmul, latmul=latmul, we=we,
        ep=None if epx is None else ep)
    specs = ([het_mod.parse_straggler(x) for x in keys["s"]],
             [het_mod.parse_fault(x) for x in keys["f"]])
    return points, {"pi": polidx, "sti": stidx, "fli": fidx}, specs


def scenario_labels(scenarios: Sequence[Scenario]) -> dict[str, np.ndarray]:
    """Per-scenario label columns (object arrays) for a scenario list —
    the list front end's counterpart of the grid's per-axis value
    arrays.  Shared with :func:`repro.core.batched_jax.eval_scenarios_jax`."""
    return {
        "workload": np.array([s.workload for s in scenarios], dtype=object),
        "cluster": np.array([s.cluster for s in scenarios], dtype=object),
        "n_workers": np.array([s.n_workers for s in scenarios],
                              dtype=np.int64),
        "policy": np.array([s.policy for s in scenarios], dtype=object),
        "collective": np.array([s.collective for s in scenarios],
                               dtype=object),
        "interconnect": np.array(
            [normalize_interconnect(s.interconnect) for s in scenarios],
            dtype=object),
        "het": np.array([het_mod.normalize_het(s.het) for s in scenarios],
                        dtype=object),
        "straggler": np.array(
            [het_mod.normalize_straggler(s.straggler) for s in scenarios],
            dtype=object),
        "sync_k": np.array(
            [normalize_sync_k(s.sync_k) for s in scenarios],
            dtype=np.int64),
        "faults": np.array(
            [het_mod.normalize_fault(s.faults) for s in scenarios],
            dtype=object),
        "ep_size": np.array([s.ep_size for s in scenarios], dtype=np.int64),
    }


def eval_scenarios_table(scenarios: Sequence[Scenario],
                         seed: int = 0) -> dict[str, np.ndarray]:
    """Columnar result table (input order) for a list of
    batched-path-eligible scenarios (closed-form or bucket-timeline
    policies); one Python pass to build code vectors, then the same
    two-tier kernel the grid front end uses (with the identity
    scenario -> kernel-point map).  ``seed`` keys the straggler Monte
    Carlo draws for stochastic scenarios.

    Raises ``ValueError`` if any scenario's policy has neither form —
    callers (:func:`repro.core.sweep.sweep`) partition first.
    """
    points, codes, specs = scenario_points(scenarios)
    cols = _policy_select(points.pax, codes["pi"], _kernel_cols(points),
                          kidx=None)
    _apply_mc_tails(points, codes, specs, cols, seed)
    return select_to_columns(cols, scenario_labels(scenarios))


def eval_scenarios(scenarios: Sequence[Scenario],
                   seed: int = 0) -> list[dict]:
    """Batched rows (input order) for a scenario list — the per-row
    view of :func:`eval_scenarios_table`."""
    if not scenarios:
        return []
    return rows_from_table(eval_scenarios_table(scenarios, seed=seed))
