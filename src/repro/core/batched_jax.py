"""JAX-native batched sweep kernels: one ``jit`` from codes to columns.

The NumPy engine (:mod:`repro.core.batched`) evaluates a grid in two
tiers — a policy-independent affine kernel reduced to ``(K,)`` cost
columns, then a cheap per-scenario policy select.  This module runs
the *same* two tiers through XLA as **one compiled function** over
whole code vectors (no ``vmap`` round trip, no per-point closures):

* tier 1 mirrors :func:`repro.core.batched._kernel_cols` — the affine
  collective coefficients (:mod:`repro.core.hardware` ``*_coeffs``),
  the unique-compute-row backward tables (structure precomputed on the
  host by :func:`repro.core.batched._compute_row_map`, gathered on
  device) and the fused multiply-add + masked-max residuals;
* tier 2 mirrors :func:`repro.core.batched._policy_select` — the same
  ``where``/``maximum`` equation select over ``(S,)`` vectors;
* the composition is one ``jit``-compiled function whose array inputs
  (axis tables, code vectors) are ordinary pytree arguments — same
  shapes, same compilation, fresh numbers every call — and whose
  output is exactly the numeric result columns, so ``backend="jax"``
  end-to-end cost is the kernel plus host label gathers.

There is no parallel formula implementation to keep in lockstep: the
affine coefficients come from the same dtype-polymorphic
:mod:`repro.core.hardware` functions the NumPy kernel calls, and the
per-workload prefix/suffix tables (``cumgrad``/``cumcount``, bucket
suffix sums via :func:`repro.core.bucketsim.suffix_tables`) are the
NumPy engine's own host-side arrays, shipped in as pytree inputs.
Numerics run in float64 under a scoped
``jax.enable_x64(True)`` context (never the global flag, which would
leak into the repo's other jax code), which is what makes the <= 1e-6
differential agreement against the NumPy oracle achievable; the
differential suite (``tests/test_batched_jax.py``) pins it on every
built-in grid.

Scenario-axis sharding: with more than one device (or an explicit
``mesh=``), the kernel and scenario code vectors are zero-padded to a
device-count multiple and placed with a ``NamedSharding`` over the
data axis of a :func:`repro.launch.mesh.make_dp_mesh` mesh — ``jit``
then partitions both tiers across devices, and the padding rows are
sliced off the gathered result.  The tiny unique-row tables stay
replicated.

Differentiability: the continuous inputs — link bandwidths/latencies
per ``(cluster, interconnect)`` pair and the bucket sizes — are
exposed as a params dict (:func:`default_params`), and
:func:`iteration_time_fn` returns a jit-compiled function of them
suitable for ``jax.grad``.  Iteration time is *piecewise constant* in
``bucket_bytes`` (the bucket size enters only through the partition
boundaries, which are discrete), so its exact gradient is 0 almost
everywhere — ``jax.grad`` returns exactly that 0, matching central
finite differences on the NumPy path whenever the perturbation stays
inside one partition cell.  :func:`numpy_iteration_times` is the
NumPy twin over the same params (bucket partitions *rebuilt* from the
perturbed sizes), which is what the finite-difference tests and the
CI agreement gate evaluate.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterable, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import analytical, batched, bucketsim
from repro.core.batched import grid_evaluator
from repro.core.hardware import (hierarchical_allreduce_coeffs,
                                 ring_allreduce_coeffs,
                                 tree_allreduce_coeffs)
from repro.core.resulttable import METHOD_LABELS, rows_from_table
from repro.core.scenarios import Scenario, ScenarioGrid

#: Continuous model inputs exposed to ``jax.grad`` — per
#: ``(cluster, interconnect)`` pair link parameters plus the bucket
#: sizes of the grid's timeline specs.
PARAM_KEYS = ("intra_bw", "intra_lat", "inter_bw", "inter_lat",
              "bucket_bytes")

#: Numeric columns shared with the NumPy engine's policy select.
_NUMERIC_COLS = ("batch", "iteration_time_s", "samples_per_sec",
                 "speedup", "t_comm_s", "t_comp_s")


# ----------------------------------------------------------------------
# Structure extraction: axis tables -> one flat dict of arrays (a jit
# pytree argument), prefix/suffix and bucket structure included.
# ----------------------------------------------------------------------
def _axes_tables(wax, cax, pax, wtab) -> tuple[dict, dict]:
    """``(tables, pflags)`` array dicts from the NumPy engine's axis
    dataclasses — the jit kernel's pytree inputs, including the
    per-workload prefix tables the affine formulation gathers
    (``cumgrad``/``cumcount`` and their totals) and the bucket suffix
    tables per timeline spec.  ``bucket_bytes`` rides along purely as
    a differentiation input: the partition structure (``bt<i>_*``) is
    discrete and prebuilt, which is exactly the piecewise-constant
    dependence documented in the module docstring.

    ``wtab`` is the padded per-worker table
    (:func:`repro.core.het.worker_table_rows`) over the unique
    ``(het profile, n_workers)`` pairs: the kernel reduces the gathered
    rows with :func:`repro.core.analytical.worker_bottleneck` *inside*
    the jit, so the het link derating shards and differentiates with
    everything else.  On all-homogeneous inputs every row is ones (the
    pads are neutral) and the reduction multiplies by exactly 1.0 —
    bit-identity, same contract as the NumPy kernel's ``None`` path."""
    grad = wax.grad_bytes
    comm_mask = (grad > 0).astype(np.float64)
    cumgrad = np.cumsum(grad, axis=1)
    cumcount = np.cumsum(comm_mask, axis=1)
    tables = {
        "flops": wax.flops, "tf_meas": wax.tf_meas, "tb_meas": wax.tb_meas,
        "bwd_ratio": wax.bwd_ratio,
        "batch_default": wax.batch_default,
        "bytes_per_sample": wax.bytes_per_sample,
        "param_bytes": wax.param_bytes, "t_io_meas": wax.t_io_meas,
        "has_meas_io": wax.has_meas_io,
        "comm_mask": comm_mask, "cumgrad": cumgrad, "cumcount": cumcount,
        "gradsum": cumgrad[:, -1], "ncomm": cumcount[:, -1],
        "intra_bw": cax.intra_bw, "intra_lat": cax.intra_lat,
        "inter_bw": cax.inter_bw, "inter_lat": cax.inter_lat,
        "gpn": cax.gpn, "disk_lat": cax.disk_lat, "disk_bw": cax.disk_bw,
        "h2d_lat": cax.h2d_lat, "h2d_bw": cax.h2d_bw,
        "rate": cax.rate, "hbm_bw": cax.hbm_bw,
        "bucket_bytes": np.array([bb for bb, _ in pax.tl_specs],
                                 dtype=np.float64),
        "w_inv": wtab["inv_speed"], "w_bw": wtab["bw_mult"],
        "w_lat": wtab["lat_mult"],
    }
    for i, (bb, _) in enumerate(pax.tl_specs):
        bt = bucketsim.bucket_table(wax.grad_bytes, bb)
        sufnb, sufcnt = bucketsim.suffix_tables(bt)
        tables[f"bt{i}_release"] = bt.release_layer
        tables[f"bt{i}_mask"] = bt.mask.astype(np.float64)
        tables[f"bt{i}_sufnb"] = sufnb
        tables[f"bt{i}_sufcnt"] = sufcnt
    pflags = {"overlap_io": pax.overlap_io,
              "overlap_comm": pax.overlap_comm,
              "h2d_early": pax.h2d_early,
              "tl_spec": pax.tl_spec}
    return tables, pflags


# ----------------------------------------------------------------------
# Tier 1: the affine kernel over whole code vectors.
# ----------------------------------------------------------------------
def _kernel_cols_jax(tbl: dict, kcodes: dict, ucodes: dict,
                     tl_overlaps: tuple, coll_codes: tuple) -> dict:
    """Policy-independent ``(K,)`` cost columns, traced on whole code
    vectors — the jax twin of :func:`repro.core.batched._kernel_cols`:
    affine collective coefficients, unique-compute-row backward tables
    gathered through the host-precomputed ``uk`` map, and the fused
    multiply-add + masked-max residuals.

    Heterogeneity enters exactly as in the NumPy kernel:
    ``ucodes["tmul"]`` (slowest-worker compute multiplier, folded into
    the unique-row key on the host) scales ``t_f``/``t_b``, and the
    per-point link multipliers — reduced in-jit from the padded worker
    table gathered at ``kcodes["hk"]`` — derate both link levels
    before the collective dispatch.  All-ones multipliers are
    bit-identity (IEEE ``x * 1.0 == x``)."""
    w, c = kcodes["w"], kcodes["c"]
    coll, n, batch, uk = kcodes["coll"], kcodes["n"], kcodes["batch"], \
        kcodes["uk"]
    hk = kcodes["hk"]
    uw, uc, ub, ut = ucodes["w"], ucodes["c"], ucodes["batch"], \
        ucodes["tmul"]
    batch_f = jnp.where(batch > 0, batch,
                        tbl["batch_default"][w]).astype(jnp.float64)
    n_f = n.astype(jnp.float64)

    # compute costs: (U, L) on the unique compute rows only
    ubatch_f = jnp.where(ub > 0, ub,
                         tbl["batch_default"][uw]).astype(jnp.float64)
    tfa = tbl["flops"][uw] * ubatch_f[:, None] / tbl["rate"][uc][:, None]
    scale = (ubatch_f / tbl["batch_default"][uw])[:, None]
    t_f = tfa + tbl["tf_meas"][uw] * scale         # measured rows: exact,
    t_b = tbl["bwd_ratio"][uw][:, None] * tfa \
        + tbl["tb_meas"][uw] * scale               # others +0.0
    t_f = t_f * ut[:, None]            # slowest-worker compute multiplier
    t_b = t_b * ut[:, None]
    prefix_b = jnp.cumsum(t_b, axis=1)
    total_b_u = prefix_b[:, -1]
    suffix_b_u = (total_b_u[:, None] - prefix_b) + t_b   # inclusive
    comp_u = t_f.sum(axis=1) + t_b.sum(axis=1)
    total_b = total_b_u[uk]

    # per-point affine collective coefficients (coll is traced; the
    # codes *present* are static, so only those models trace).  The
    # heterogeneous collective is gated by its slowest link, so both
    # link levels are derated before the algorithm dispatch.
    _, bwmul, latmul = analytical.worker_bottleneck(
        tbl["w_inv"][hk], tbl["w_bw"][hk], tbl["w_lat"][hk])
    intra_bw = tbl["intra_bw"][c] * bwmul
    intra_lat = tbl["intra_lat"][c] * latmul
    inter_bw = tbl["inter_bw"][c] * bwmul
    inter_lat = tbl["inter_lat"][c] * latmul
    use_intra = n <= tbl["gpn"][c]
    link_bw = jnp.where(use_intra, intra_bw, inter_bw)
    link_lat = jnp.where(use_intra, intra_lat, inter_lat)

    def _model(code: int):
        if code == 0:
            return ring_allreduce_coeffs(n_f, link_bw, link_lat)
        if code == 1:
            return tree_allreduce_coeffs(n, link_bw, link_lat)
        return hierarchical_allreduce_coeffs(
            n, tbl["gpn"][c], intra_bw, intra_lat, inter_bw, inter_lat)

    per_byte, per_message = _model(coll_codes[0])
    for code in coll_codes[1:]:
        a, b = _model(code)
        sel = coll == code
        per_byte = jnp.where(sel, a, per_byte)
        per_message = jnp.where(sel, b, per_message)

    # pipeline terms: (K,)
    nbytes_in = batch_f * tbl["bytes_per_sample"][w]
    t_io = tbl["disk_lat"][c] + nbytes_in / tbl["disk_bw"][c]
    t_io = jnp.where(tbl["has_meas_io"][w],
                     tbl["t_io_meas"][w] * batch_f / tbl["batch_default"][w],
                     t_io)
    t_h2d = tbl["h2d_lat"][c] + nbytes_in / tbl["h2d_bw"][c]

    # WFBP residual (affine form — see the NumPy kernel's derivation)
    cand = suffix_b_u[uk] \
        + per_byte[:, None] * tbl["cumgrad"][w] \
        + per_message[:, None] * tbl["cumcount"][w]
    cand = cand * tbl["comm_mask"][w]
    out = {
        "io_h2d": t_io + t_h2d,
        "t_h2d": t_h2d,
        "comp": comp_u[uk],
        "sum_c": per_byte * tbl["gradsum"][w] + per_message * tbl["ncomm"][w],
        "tc_no": jnp.maximum(cand.max(axis=1, initial=0.0) - total_b, 0.0),
        "t_u": 3.0 * tbl["param_bytes"][w] / tbl["hbm_bw"][c],
        "n_f": n_f,
        "batch_f": batch_f,
    }
    for i, ov_comm in enumerate(tl_overlaps):
        if ov_comm:
            release_u = jnp.take_along_axis(
                suffix_b_u, tbl[f"bt{i}_release"][uw], axis=1)
        else:
            release_u = jnp.broadcast_to(
                total_b_u[:, None],
                (len(uw), tbl[f"bt{i}_release"].shape[1]))
        cand = release_u[uk] \
            + per_byte[:, None] * tbl[f"bt{i}_sufnb"][w] \
            + per_message[:, None] * tbl[f"bt{i}_sufcnt"][w]
        cand = cand * tbl[f"bt{i}_mask"][w]
        out[f"tl{i}"] = jnp.maximum(
            cand.max(axis=1, initial=0.0) - total_b, 0.0)
    return out


# ----------------------------------------------------------------------
# Tier 2: the policy select over whole scenario vectors.
# ----------------------------------------------------------------------
def _select_jax(pflags: dict, tl_overlaps: tuple, kc: dict, pi, kidx):
    """The jax twin of :func:`repro.core.batched._policy_select` (same
    equations, same zero-comm weak-scaling baseline), over whole
    ``(S,)`` vectors; method labels are strings and stay on the host
    side."""
    def g(name):
        return kc[name][kidx]

    ov_io = pflags["overlap_io"][pi]
    ov_comm = pflags["overlap_comm"][pi]
    early = pflags["h2d_early"][pi]

    comm_term = jnp.where(ov_comm, g("tc_no"), g("sum_c"))
    spec_of = pflags["tl_spec"][pi]
    for i, _ in enumerate(tl_overlaps):
        comm_term = jnp.where(spec_of == i, g(f"tl{i}"), comm_term)
    gpu_chain = g("comp") + comm_term + g("t_u")
    io_h2d, t_h2d = g("io_h2d"), g("t_h2d")
    eq2 = io_h2d + gpu_chain
    eq_early = jnp.maximum(io_h2d, gpu_chain)
    eq_late = jnp.maximum(io_h2d, t_h2d + gpu_chain)
    t_iter = jnp.where(~ov_io, eq2, jnp.where(early, eq_early, eq_late))

    base_chain = g("comp") + g("t_u")
    t1 = jnp.where(~ov_io, io_h2d + base_chain,
                   jnp.where(early, jnp.maximum(io_h2d, base_chain),
                             jnp.maximum(io_h2d, t_h2d + base_chain)))
    n_f, batch_f = g("n_f"), g("batch_f")
    return {
        "batch": batch_f,
        "iteration_time_s": t_iter,
        "samples_per_sec": n_f * batch_f / t_iter,
        "speedup": n_f * t1 / t_iter,
        "t_comm_s": g("sum_c"),
        "t_comp_s": g("comp"),
    }


@functools.partial(jax.jit, static_argnames=("tl_overlaps", "coll_codes"))
def _columns_jax(tables: dict, pflags: dict, kcodes: dict, scodes: dict,
                 ucodes: dict, tl_overlaps: tuple,
                 coll_codes: tuple) -> dict:
    """The whole two-tier evaluation — codes in, result columns out —
    as one compiled function.  Compilation is keyed by array
    shapes/dtypes and the static ``tl_overlaps``/``coll_codes``
    tuples — re-running a grid (or any same-shaped grid) with fresh
    numbers reuses the executable."""
    kc = _kernel_cols_jax(tables, kcodes, ucodes, tl_overlaps, coll_codes)
    return _select_jax(pflags, tl_overlaps, kc, scodes["pi"],
                       scodes["kidx"])


# ----------------------------------------------------------------------
# Sharding: pad the batch axes to a device-count multiple and place
# the code vectors over the mesh's data axis.
# ----------------------------------------------------------------------
#: Benign fill for padding rows (index 0 is always valid; n=1 is the
#: zero-comm degenerate; batch=0 means "table default").
_PAD_FILL = {"n": 1}


def _shard_codes(codes: dict, mesh) -> dict:
    ndev = math.prod(mesh.devices.shape)
    axis = mesh.axis_names[0]
    sharding = jax.sharding.NamedSharding(
        mesh, jax.sharding.PartitionSpec(axis))
    size = len(next(iter(codes.values())))
    pad = (-size) % ndev
    out = {}
    for k, v in codes.items():
        if pad:
            fill = np.full(pad, _PAD_FILL.get(k, 0), dtype=v.dtype)
            v = np.concatenate([v, fill])
        out[k] = jax.device_put(v, sharding)
    return out


# ----------------------------------------------------------------------
# Grid front end.
# ----------------------------------------------------------------------
class JaxGridEvaluator:
    """A :class:`ScenarioGrid` prepared for the fused jit kernel.

    Reuses the NumPy engine's memoized structure (axis tables, code
    vectors, label arrays, unique-compute-row map) — only the numeric
    evaluation moves to XLA.  Raises ``ValueError`` for grids
    containing simulator-only policies: unlike the NumPy engine there
    is no event-driven fallback to interleave, and silently falling
    back would defeat the point of selecting the backend explicitly.

    ``mesh=None`` autoselects: a data-parallel mesh over all devices
    when more than one is visible, unsharded otherwise.  Pass a mesh
    (e.g. :func:`repro.launch.mesh.make_dp_mesh`) to force sharding —
    a single-device mesh exercises the sharded path end to end.
    """

    def __init__(self, grid: ScenarioGrid, *, mesh=None):
        ev = grid_evaluator(grid)
        if not ev.all_batched:
            bad = [name for name, f, t in zip(
                ev._pax.names, ev._pax.has_fast, ev._pax.has_tl)
                if not (bool(f) or bool(t))]
            raise ValueError(
                f"backend='jax' evaluates closed-form and bucket-timeline "
                f"policies only; {bad} need the event-driven simulator. "
                f"Use backend='numpy' for grids containing them.")
        self.ev = ev
        self._tables, self._pflags = _axes_tables(ev._wax, ev._cax,
                                                  ev._pax, ev._wtab)
        self._tl_overlaps = tuple(bool(ov) for _, ov in ev._pax.tl_specs)
        self._coll_codes = tuple(int(x) for x in np.unique(ev._kcoll)) or (0,)
        uw, uc, ub, ut, uk = batched._compute_row_map(
            ev._wax, ev._cax, ev._kwidx, ev._kcidx, ev._kbatch, ev._ktmul)
        kcodes = {"w": ev._kwidx, "c": ev._kcidx, "coll": ev._kcoll,
                  "n": ev._kn, "batch": ev._kbatch, "uk": uk,
                  "hk": ev._khk}
        self._ucodes = {"w": uw, "c": uc, "batch": ub,
                        "tmul": np.ones(len(uw)) if ut is None else ut}
        S = len(ev)
        if S:
            sc = ev._scenario_codes(0, S)
            scodes = {"pi": sc["pi"], "kidx": sc["kidx"]}
        else:
            scodes = {"pi": np.empty(0, dtype=np.int64),
                      "kidx": np.empty(0, dtype=np.int64)}
        if mesh is None and len(jax.devices()) > 1:
            from repro.launch.mesh import make_dp_mesh
            mesh = make_dp_mesh(len(jax.devices()))
        self.mesh = mesh
        if mesh is not None and S:
            with jax.enable_x64(True):
                kcodes = _shard_codes(kcodes, mesh)
                scodes = _shard_codes(scodes, mesh)
        self._kcodes, self._scodes = kcodes, scodes

    def __len__(self) -> int:
        return len(self.ev)

    def columns(self, params: dict | None = None) -> dict[str, np.ndarray]:
        """All numeric result columns as host float64 ``(S,)`` arrays
        (blocks on the device computation).  ``params`` optionally
        overrides the :data:`PARAM_KEYS` entries."""
        S = len(self.ev)
        if S == 0:
            return {k: np.empty(0) for k in _NUMERIC_COLS}
        with jax.enable_x64(True):
            out = self._traced_columns(params)
            return {k: np.asarray(v)[:S] for k, v in out.items()
                    if k in _NUMERIC_COLS}

    def _traced_columns(self, params: dict | None = None) -> dict:
        """The jit call itself — kept separate so the differentiable
        front end (:func:`iteration_time_fn`) can trace through it.
        Callers are responsible for the ``jax.enable_x64(True)`` scope."""
        tables = self._tables
        if params:
            unknown = set(params) - set(PARAM_KEYS)
            if unknown:
                raise ValueError(f"unknown param keys {sorted(unknown)}; "
                                 f"differentiable params are {PARAM_KEYS}")
            tables = {**tables, **params}
        return _columns_jax(tables, self._pflags, self._kcodes,
                            self._scodes, self._ucodes, self._tl_overlaps,
                            self._coll_codes)

    def run(self, params: dict | None = None, seed: int = 0) -> "JaxGridRun":
        """One evaluation: the jit kernel for the deterministic
        columns, then the straggler Monte Carlo tail pass.  The MC
        orchestration (dedup, keyed draws, slowest-worker fold,
        ``np.quantile`` reduction) is the host-side pass *shared* with
        the NumPy engine (:func:`repro.core.batched._apply_mc_tails`),
        which is what guarantees draw-for-draw agreement between the
        backends; deterministic grids skip it and the tail columns
        equal ``iteration_time_s`` bit-exactly."""
        cols = self.columns(params)
        ev = self.ev
        if ev._any_mc and len(ev):
            codes = ev._scenario_codes(0, len(ev))
            k = codes["kidx"]
            batched._apply_mc_tails(
                ev._wax, ev._cax, ev._pax, ev._kwidx[k], ev._kcidx[k],
                ev._kcoll[k], ev._kn[k], ev._kbatch[k], codes["pi"],
                ev._khk[k], ev._wtab,
                None if ev._kbwmul is None else ev._kbwmul[k],
                None if ev._klatmul is None else ev._klatmul[k],
                ev._st_specs, codes["sti"], cols, seed,
                synck=ev._ksynck[k], ft_specs=ev._ft_specs,
                fidx=codes["fli"])
        else:
            t_iter = cols["iteration_time_s"]
            cols["t_mean_s"] = t_iter
            cols["t_p95_s"] = t_iter
            cols["t_p99_s"] = t_iter
        return JaxGridRun(self, cols)

    def method_labels(self, pi: np.ndarray) -> list[str]:
        """Per-row evaluation-path labels (``all_batched`` holds, so
        only the two batched labels occur)."""
        return METHOD_LABELS[self.ev._pax.tier[pi]].tolist()


class JaxGridRun:
    """One evaluation of a grid on the jax backend: host-side numeric
    columns plus the shared structure, materializing columnar result
    tables chunk by chunk — the jax twin of
    :class:`repro.core.batched.GridRun` (no simulator rows:
    simulator-only grids are rejected up front)."""

    def __init__(self, jev: JaxGridEvaluator, cols: dict[str, np.ndarray]):
        self._jev = jev
        self._cols = cols

    def __len__(self) -> int:
        return len(self._jev)

    def columns_slice(self, lo: int, hi: int) -> dict[str, np.ndarray]:
        ev = self._jev.ev
        out = {k: v[lo:hi] for k, v in self._cols.items()}
        out["method"] = self._jev.method_labels(
            ev._scenario_codes(lo, hi)["pi"])
        return out

    def table_slice(self, lo: int, hi: int):
        """Columnar result table for flat scenario indices ``[lo, hi)``
        in grid order — the jax twin of
        :meth:`repro.core.batched.GridRun.table_slice` (the ``batched``
        mask is all-true by construction)."""
        ev = self._jev.ev
        codes = ev._scenario_codes(lo, hi)
        cols = {k: v[lo:hi] for k, v in self._cols.items()}
        cols["method_code"] = ev._pax.tier[codes["pi"]]
        return (batched.select_to_columns(cols, ev._label_columns(codes)),
                codes["batched"])

    def rows_slice(self, lo: int, hi: int) -> list[dict]:
        table, _ = self.table_slice(lo, hi)
        return rows_from_table(table)


#: Structure memo, mirroring :func:`repro.core.batched.grid_evaluator`
#: (separate because the jax evaluator also holds device-side codes).
_JAX_MEMO: dict = {}
_MEMO_LIMIT = 64


def jax_grid_evaluator(grid: ScenarioGrid, *, mesh=None) -> JaxGridEvaluator:
    """Memoized :class:`JaxGridEvaluator` (unsharded/auto mesh only —
    explicit meshes always build fresh)."""
    if mesh is not None:
        return JaxGridEvaluator(grid, mesh=mesh)
    try:
        from repro.core.workloads import resolve_workload
        tables = tuple(resolve_workload(w) for w in grid.workloads)
        key = (grid, tuple(id(t) for t in tables))
        hash(key)
    except TypeError:
        return JaxGridEvaluator(grid)
    hit = _JAX_MEMO.get(key)
    if hit is not None:
        return hit[0]
    if len(_JAX_MEMO) >= _MEMO_LIMIT:
        _JAX_MEMO.clear()
    jev = JaxGridEvaluator(grid)
    _JAX_MEMO[key] = (jev, tables)
    return jev


def jax_evaluator_cached(grid: ScenarioGrid) -> bool:
    """True when :func:`jax_grid_evaluator` would hit the structure
    memo — the jax twin of :func:`repro.core.batched.evaluator_cached`
    (a pure probe; the sweep service's cache-hit accounting)."""
    try:
        from repro.core.workloads import resolve_workload
        tables = tuple(resolve_workload(w) for w in grid.workloads)
        key = (grid, tuple(id(t) for t in tables))
        hash(key)
    except (TypeError, ValueError):
        return False
    return key in _JAX_MEMO


# ----------------------------------------------------------------------
# Scenario-list front end — jax twin of batched.eval_scenarios_table.
# ----------------------------------------------------------------------
def eval_scenarios_table_jax(
        scenarios: Sequence[Scenario] | Iterable[Scenario],
        seed: int = 0) -> dict[str, np.ndarray]:
    """Columnar result table (input order) for a list of
    batched-path-eligible scenarios, evaluated by the fused jit kernel
    with the identity scenario -> kernel-point map; het/straggler
    structure comes from the shared
    :func:`repro.core.batched.scenario_het_axes` pass and the straggler
    Monte Carlo tails from the shared host-side pass, exactly as on the
    grid path — which is what makes a *concatenation* of several
    queries' scenario lists bit-identical, column for column, to
    sweeping each query's grid directly (the sweep service's coalescer
    contract, pinned by ``tests/test_service.py``).  Raises
    ``ValueError`` (via :func:`repro.core.batched.scenario_axes`) if
    any scenario's policy has neither a closed nor a bucket-timeline
    form."""
    from repro.core.resulttable import empty_table

    scenarios = list(scenarios)
    if not scenarios:
        return empty_table()
    wax, cax, pax, widx, cidx, polidx, coll, n, batch = \
        batched.scenario_axes(scenarios)
    (hks, wtab, tmul, bwmul, latmul, st_specs, stidx,
     synck, ft_specs, fidx) = batched.scenario_het_axes(scenarios)
    tables, pflags = _axes_tables(wax, cax, pax, wtab)
    tl_overlaps = tuple(bool(ov) for _, ov in pax.tl_specs)
    S = len(scenarios)
    uw, uc, ub, ut, uk = batched._compute_row_map(wax, cax, widx, cidx,
                                                  batch, tmul)
    kcodes = {"w": widx, "c": cidx, "coll": coll, "n": n, "batch": batch,
              "uk": uk, "hk": hks}
    ucodes = {"w": uw, "c": uc, "batch": ub,
              "tmul": np.ones(len(uw)) if ut is None else ut}
    scodes = {"pi": polidx, "kidx": np.arange(S, dtype=np.int64)}
    coll_codes = tuple(int(x) for x in np.unique(coll)) or (0,)
    with jax.enable_x64(True):
        out = _columns_jax(tables, pflags, kcodes, scodes, ucodes,
                           tl_overlaps, coll_codes)
        cols = {k: np.asarray(v) for k, v in out.items()
                if k in _NUMERIC_COLS}
    batched._apply_mc_tails(wax, cax, pax, widx, cidx, coll, n, batch,
                            polidx, hks, wtab, bwmul, latmul, st_specs,
                            stidx, cols, seed, synck=synck,
                            ft_specs=ft_specs, fidx=fidx)
    cols["method_code"] = pax.tier[polidx]
    return batched.select_to_columns(cols,
                                     batched.scenario_labels(scenarios))


def eval_scenarios_jax(scenarios: Sequence[Scenario] | Iterable[Scenario],
                       seed: int = 0) -> list[dict]:
    """Batched rows (input order) for a scenario list — the per-row
    view of :func:`eval_scenarios_table_jax`."""
    return rows_from_table(eval_scenarios_table_jax(scenarios, seed=seed))


# ----------------------------------------------------------------------
# Differentiable front end.
# ----------------------------------------------------------------------
def default_params(grid: ScenarioGrid) -> dict[str, np.ndarray]:
    """The grid's resolved continuous inputs (:data:`PARAM_KEYS`):
    per-pair link bandwidths/latencies and per-timeline-spec bucket
    sizes — the point :func:`iteration_time_fn` differentiates
    around."""
    jev = jax_grid_evaluator(grid)
    return {k: np.array(jev._tables[k], dtype=np.float64, copy=True)
            for k in PARAM_KEYS}


def iteration_time_fn(grid: ScenarioGrid):
    """``(f, params0)``: ``f(params) -> (S,)`` iteration times, jit
    compiled and differentiable w.r.t. every :data:`PARAM_KEYS` entry.
    Call (and differentiate) ``f`` inside a
    ``jax.enable_x64(True)`` scope, or use the
    :func:`grad_iteration_time` convenience wrapper.

    The gradient w.r.t. ``bucket_bytes`` is exactly 0: iteration time
    is piecewise constant in the bucket size (see the module
    docstring), and ``f`` holds the partition fixed at ``params0``'s
    structure.  :func:`numpy_iteration_times` *rebuilds* the partition
    per call, so central differences on it recover the same 0 inside a
    partition cell."""
    jev = jax_grid_evaluator(grid)
    S = len(jev)

    def f(params: dict):
        return jev._traced_columns(params)["iteration_time_s"][:S]

    return f, default_params(grid)


def grad_iteration_time(grid: ScenarioGrid,
                        params: dict | None = None) -> dict[str, np.ndarray]:
    """``d(sum of iteration times)/d(params)`` as host arrays — the
    end-to-end differentiability surface the gradient-correctness
    tests pin against NumPy central differences."""
    f, p0 = iteration_time_fn(grid)
    if params:
        p0 = {**p0, **params}
    with jax.enable_x64(True):
        p = {k: jnp.asarray(v, dtype=jnp.float64) for k, v in p0.items()}
        g = jax.grad(lambda q: f(q).sum())(p)
        return {k: np.asarray(v) for k, v in g.items()}


def numpy_iteration_times(grid: ScenarioGrid,
                          params: dict | None = None) -> np.ndarray:
    """The NumPy oracle over the same params surface: link overrides
    swap into the cluster axis, bucket-size overrides *rebuild* the
    bucket partitions.  This is the finite-difference reference for
    :func:`grad_iteration_time` and the numeric side of the CI
    agreement gate."""
    ev = grid_evaluator(grid)
    cax = ev._cax
    tl_specs = list(ev._pax.tl_specs)
    if params:
        link = {k: np.asarray(params[k], dtype=np.float64)
                for k in ("intra_bw", "intra_lat", "inter_bw", "inter_lat")
                if k in params}
        if link:
            cax = dataclasses.replace(cax, **link)
        if "bucket_bytes" in params:
            bb = np.asarray(params["bucket_bytes"], dtype=np.float64)
            tl_specs = [(float(bb[i]), ov)
                        for i, (_, ov) in enumerate(tl_specs)]
    kc = batched._kernel_cols(ev._wax, cax, ev._kwidx, ev._kcidx,
                              ev._kcoll, ev._kn, ev._kbatch,
                              tl_specs=tl_specs, tmul=ev._ktmul,
                              bwmul=ev._kbwmul, latmul=ev._klatmul)
    codes = ev._scenario_codes(0, len(ev))
    return batched._policy_select(ev._pax, codes["pi"], kc,
                                  codes["kidx"])["iteration_time_s"]
