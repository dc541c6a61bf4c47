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
  (axis tables, code vectors) arrive packed in two host buffers, a
  float64 one and an int32 one — same shapes, same compilation, fresh
  numbers every call — and whose one output packs the numeric result
  columns into a single buffer, so ``backend="jax"`` end-to-end cost is
  two host-to-device transfers, the kernel, one device-to-host
  transfer and host label gathers.

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

from repro.core import analytical, batched, bucketsim, obs
from repro.core.batched import grid_evaluator
from repro.core.hardware import (alltoall_coeffs,
                                 hierarchical_allreduce_coeffs,
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
def _kernel_args(points: batched.KernelPoints) -> tuple:
    """``(tables, pflags, kcodes, ucodes, tl_overlaps, coll_codes)``:
    the jit kernel's arguments for a :class:`repro.core.batched.KernelPoints`,
    all but the scenario codes.  ``tables`` holds the axis tables as
    arrays, including the per-workload prefix tables the affine
    formulation gathers (``cumgrad``/``cumcount`` and their totals), the
    bucket suffix tables per timeline spec and, on a batch with some
    ``ep > 1``, the expert-parallel tables as ``ep_*`` entries (with the
    codes ``we``/``ep`` in ``kcodes``).  ``bucket_bytes`` rides along
    purely as a differentiation input: the partition structure
    (``bt<i>_*``) is discrete and prebuilt, which is exactly the
    piecewise-constant dependence documented in the module docstring.

    ``w_inv``/``w_bw``/``w_lat`` are the padded per-worker table
    (:func:`repro.core.het.worker_table_rows`) over the unique
    ``(het profile, n_workers)`` pairs: the kernel reduces the gathered
    rows with :func:`repro.core.analytical.worker_bottleneck` *inside*
    the jit, so the het link derating shards and differentiates with
    everything else.  On all-homogeneous inputs every row is ones (the
    pads are neutral) and the reduction multiplies by exactly 1.0 —
    bit-identity, same contract as the NumPy kernel's ``None`` path.
    ``ucodes`` are the unique compute rows
    (:func:`repro.core.batched._compute_row_map`)."""
    wax, cax, pax, wtab = points.wax, points.cax, points.pax, points.wtab
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
    uw, uc, ub, ut, uk = batched._compute_row_map(points)
    kcodes = {"w": points.w, "c": points.c, "coll": points.coll,
              "n": points.n, "batch": points.batch, "uk": uk,
              "hk": points.hk}
    epx = points.epx
    if epx is not None:
        tables.update({"ep_dcum": epx.dcum, "ep_dcnt": epx.dcnt,
                       "ep_ecum": epx.ecum, "ep_ecnt": epx.ecnt,
                       "ep_param_bytes": epx.param_bytes,
                       "ep_a2a_suf": epx.a2a_suf,
                       "ep_a2a_sufc": epx.a2a_sufc})
        for i, b in enumerate(epx.buckets):
            for name, x in zip(("release", "mask", "sd", "sdc", "se", "sec"),
                               b):
                tables[f"ep_bt{i}_{name}"] = x
        kcodes.update(we=points.we, ep=points.ep)
    ucodes = {"w": uw, "c": uc, "batch": ub,
              "tmul": np.ones(len(uw)) if ut is None else ut}
    tl_overlaps = tuple(bool(ov) for _, ov in pax.tl_specs)
    coll_codes = tuple(int(x) for x in np.unique(points.coll)) or (0,)
    return tables, pflags, kcodes, ucodes, tl_overlaps, coll_codes


def _point_counts(points: batched.KernelPoints) -> tuple[int, int]:
    """``(kernel points, those with ep > 1)`` of ``points``."""
    ep = 0 if points.ep is None else int((points.ep > 1).sum())
    return len(points.n), ep


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
    bit-identity (IEEE ``x * 1.0 == x``).

    Expert parallelism is traced only when ``kcodes`` holds its codes
    (``we``, the ``(workload, ep)`` pair row, and ``ep``, the group
    size), which the host puts there only
    for a batch with some ``ep > 1`` — the pytree's structure is static,
    so a batch without it compiles to the same program as before the
    axis existed.  Its terms are those of the NumPy kernel."""
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
    ep_on = "we" in kcodes
    if ep_on:
        pe, ep = kcodes["we"], kcodes["ep"]
        gpn = tbl["gpn"][c]
        group = n // ep
        group_gpn = jnp.maximum(gpn // ep, 1)
        e_intra = group <= group_gpn
        e_bw = jnp.where(e_intra, intra_bw, inter_bw)
        e_lat = jnp.where(e_intra, intra_lat, inter_lat)

        def _expert_model(code: int):
            if code == 0:
                return ring_allreduce_coeffs(group.astype(jnp.float64),
                                             e_bw, e_lat)
            if code == 1:
                return tree_allreduce_coeffs(group, e_bw, e_lat)
            return hierarchical_allreduce_coeffs(
                group, group_gpn, intra_bw, intra_lat, inter_bw, inter_lat)

        e_byte, e_msg = _expert_model(coll_codes[0])
        for code in coll_codes[1:]:
            a, b = _expert_model(code)
            sel = coll == code
            e_byte = jnp.where(sel, a, e_byte)
            e_msg = jnp.where(sel, b, e_msg)
        a_intra = ep <= gpn
        a_byte, a_msg = alltoall_coeffs(
            ep, jnp.where(a_intra, intra_bw, inter_bw),
            jnp.where(a_intra, intra_lat, inter_lat))
        a_byte = 2.0 * a_byte * batch_f     # two a layer, each pass
        a_msg = 2.0 * a_msg
        suffix_b = suffix_b_u[uk] \
            + a_byte[:, None] * tbl["ep_a2a_suf"][w] \
            + a_msg[:, None] * tbl["ep_a2a_sufc"][w]
        a2a_pass = a_byte * tbl["ep_a2a_suf"][w, 0] \
            + a_msg * tbl["ep_a2a_sufc"][w, 0]
        total_b = total_b + a2a_pass

    # pipeline terms: (K,)
    nbytes_in = batch_f * tbl["bytes_per_sample"][w]
    t_io = tbl["disk_lat"][c] + nbytes_in / tbl["disk_bw"][c]
    t_io = jnp.where(tbl["has_meas_io"][w],
                     tbl["t_io_meas"][w] * batch_f / tbl["batch_default"][w],
                     t_io)
    t_h2d = tbl["h2d_lat"][c] + nbytes_in / tbl["h2d_bw"][c]

    # WFBP residual (affine form — see the NumPy kernel's derivation)
    if ep_on:
        cand = suffix_b \
            + per_byte[:, None] * tbl["ep_dcum"][pe] \
            + per_message[:, None] * tbl["ep_dcnt"][pe] \
            + e_byte[:, None] * tbl["ep_ecum"][pe] \
            + e_msg[:, None] * tbl["ep_ecnt"][pe]
        cand = cand * tbl["comm_mask"][w]
        out = {
            "io_h2d": t_io + t_h2d,
            "t_h2d": t_h2d,
            "comp": comp_u[uk] + 2.0 * a2a_pass,
            "sum_c": per_byte * tbl["ep_dcum"][pe, -1]
            + per_message * tbl["ep_dcnt"][pe, -1]
            + e_byte * tbl["ep_ecum"][pe, -1]
            + e_msg * tbl["ep_ecnt"][pe, -1],
            "tc_no": jnp.maximum(cand.max(axis=1, initial=0.0) - total_b,
                                 0.0),
            "t_u": 3.0 * tbl["ep_param_bytes"][pe] / tbl["hbm_bw"][c],
            "n_f": n_f,
            "batch_f": batch_f,
        }
        for i, ov_comm in enumerate(tl_overlaps):
            rel = tbl[f"ep_bt{i}_release"]
            if ov_comm:
                cand = jnp.take_along_axis(suffix_b, rel[pe], axis=1)
            else:
                cand = jnp.broadcast_to(total_b[:, None],
                                        (len(pe), rel.shape[1]))
            cand = cand \
                + per_byte[:, None] * tbl[f"ep_bt{i}_sd"][pe] \
                + per_message[:, None] * tbl[f"ep_bt{i}_sdc"][pe] \
                + e_byte[:, None] * tbl[f"ep_bt{i}_se"][pe] \
                + e_msg[:, None] * tbl[f"ep_bt{i}_sec"][pe]
            cand = cand * tbl[f"ep_bt{i}_mask"][pe]
            out[f"tl{i}"] = jnp.maximum(
                cand.max(axis=1, initial=0.0) - total_b, 0.0)
        return out
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


def _columns(tables: dict, pflags: dict, kcodes: dict, scodes: dict,
             ucodes: dict, tl_overlaps: tuple, coll_codes: tuple) -> dict:
    """The whole two-tier evaluation — codes in, the numeric result
    columns out, as a dict of ``(S,)`` float64 arrays — traced inside
    one of the two compiled functions below."""
    kc = _kernel_cols_jax(tables, kcodes, ucodes, tl_overlaps, coll_codes)
    return _select_jax(pflags, tl_overlaps, kc, scodes["pi"],
                       scodes["kidx"])


#: :func:`_columns` compiled as it is, returning the dict: the function
#: the differentiable front end (:func:`iteration_time_fn`) traces
#: through.
_columns_dict = jax.jit(_columns, static_argnames=("tl_overlaps",
                                                   "coll_codes"))


#: Elements in one tile of a float32 row on the TPU: each word row of
#: the packed buffer starts on a tile, so no row is shifted to place it.
_TILE = 1024

#: The kernel's five dict arguments, in order; the names its errors use.
_ARG_NAMES = ("tables", "pflags", "kcodes", "scodes", "ucodes")

_INT32 = np.iinfo(np.int32)


def _packed_args(args: tuple) -> tuple:
    """The kernel's arguments (``JaxGridEvaluator._args``: five dicts,
    then ``tl_overlaps`` and ``coll_codes``) as :func:`_columns_jax`
    takes them: ``(f64, i32, live, layout, tl_overlaps, coll_codes)``.

    Every NumPy leaf is copied into one of two flat host buffers: float
    leaves into ``f64``, integer and bool leaves into ``i32``.  The
    codes are indices and counts below a few thousand, exact in int32;
    a leaf with a value outside int32 raises ``ValueError`` naming it.
    Any other leaf — a device array, such as the codes
    :func:`_shard_codes` placed over a mesh, or a tracer — passes as it
    is, in ``live``.  ``layout`` (hashable, a static argument) holds per
    dict, per key, the leaf's kind (``"f"``, ``"i"``, ``"b"`` for bool,
    ``"a"`` for a leaf of ``live``), its offset in its buffer (for
    ``"a"``, its index in ``live``) and its shape: it depends only on
    the keys and shapes, so a same-shaped grid reuses the
    executable."""
    *dicts, tl_overlaps, coll_codes = args
    floats, ints, live, layout = [], [], [], []
    nf = ni = 0
    for name, group in zip(_ARG_NAMES, dicts):
        entries = []
        for key, x in group.items():
            if not isinstance(x, np.ndarray):
                entries.append((key, "a", len(live), None))
                live.append(x)
            elif x.dtype.kind == "f":
                entries.append((key, "f", nf, x.shape))
                floats.append(x)
                nf += x.size
            else:
                if x.dtype.kind != "b" and x.size and (
                        x.min() < _INT32.min or x.max() > _INT32.max):
                    raise ValueError(
                        f"{name}[{key!r}] holds integers outside int32 "
                        f"({x.min()}..{x.max()}); the kernel's codes are "
                        f"int32")
                entries.append((key, "b" if x.dtype.kind == "b" else "i",
                                ni, x.shape))
                ints.append(x)
                ni += x.size
        layout.append(tuple(entries))
    f64 = np.empty(nf, dtype=np.float64)
    i32 = np.empty(ni, dtype=np.int32)
    for buf, leaves in ((f64, floats), (i32, ints)):
        at = 0
        for x in leaves:
            buf[at:at + x.size] = x.ravel()
            at += x.size
    return f64, i32, tuple(live), tuple(layout), tl_overlaps, coll_codes


def _unpacked_args(f64, i32, live: tuple, layout: tuple) -> tuple:
    """The five dicts of :func:`_packed_args` again, traced: static
    slices of the two buffers, reshaped; bools as ``!= 0``; the integer
    codes stay int32."""
    def leaf(kind, at, shape):
        if kind == "a":
            return live[at]
        buf = f64 if kind == "f" else i32
        x = buf[at:at + math.prod(shape)].reshape(shape)
        return x != 0 if kind == "b" else x

    return tuple({key: leaf(kind, at, shape)
                  for key, kind, at, shape in group} for group in layout)


def _host_h2d(f64: np.ndarray, i32: np.ndarray, layout: tuple) -> None:
    """Counters ``sweep.h2d_arrays``/``sweep.h2d_bytes``: the two host
    buffers of :func:`_packed_args`, and ``sweep.ep_h2d_bytes``: the
    bytes of the expert-parallel tables and codes in them (only where
    there are any)."""
    obs.count("sweep.h2d_arrays", 2)
    obs.count("sweep.h2d_bytes", f64.nbytes + i32.nbytes)
    tables, _, kcodes, _, _ = layout
    if any(key == "we" for key, *_ in kcodes):
        ep = [e for e in tables if e[0].startswith("ep_")] \
            + [e for e in kcodes if e[0] in ("we", "ep")]
        obs.count("sweep.ep_h2d_bytes", sum(
            math.prod(shape) * (8 if kind == "f" else 4)
            for _, kind, _, shape in ep if kind != "a"))


@functools.partial(jax.jit,
                   static_argnames=("layout", "tl_overlaps", "coll_codes",
                                    "shards"))
def _columns_jax(f64, i32, live: tuple, layout: tuple, tl_overlaps: tuple,
                 coll_codes: tuple, shards: int = 0):
    """:func:`_columns` as one compiled function whose one output packs
    the :data:`_NUMERIC_COLS` into a single buffer, so the host fetches
    a sweep in one transfer (:func:`_host_columns`).  Its inputs are
    packed too (:func:`_packed_args`): the float64 buffer of the
    tables, the int32 buffer of the flags and codes, and the leaves
    already on the device, unpacked here by static slices, so a sweep
    places two host buffers on the device, not one per leaf.
    Compilation is keyed by the buffers' lengths, ``layout`` and the
    other static arguments — re-running a grid (or any same-shaped
    grid) with fresh numbers reuses the executable.

    With ``shards == 0`` the buffer is the ``(6, S)`` float64 stack.
    With ``shards = n`` (for a device that emulates float64, the TPU,
    on ``n`` devices) it is the ``(n, 12 * m)`` float32 words of the
    columns (:func:`_f32_words`): row ``d`` holds the ``d``-th of ``n``
    equal blocks of the scenario axis, the block device ``d`` computes
    on a mesh, as the six leading words and then the six remainders,
    each padded to ``m``, a whole number of tiles.  So nothing moves
    between devices, and no word row is laid out anew: stacking the
    columns as rows of a 2-D float32 array cost 0.3 ms more of a
    10 ms kernel on a TPU v5e."""
    cols = _columns(*_unpacked_args(f64, i32, live, layout), tl_overlaps,
                    coll_codes)
    if not shards:
        return jnp.stack([cols[k] for k in _NUMERIC_COLS])
    his, los = zip(*(_f32_words(cols[k]) for k in _NUMERIC_COLS))
    rows = [w.reshape(shards, -1) for w in his + los]
    pad = (-rows[0].shape[1]) % _TILE
    return jnp.concatenate([jnp.pad(w, ((0, 0), (0, pad))) for w in rows],
                           axis=1)


def _f32_words(x) -> tuple:
    """``x`` (float64) as the two float32 words of a float64 that the
    device emulates as a pair of float32 (the TPU): the leading word and
    the remainder, with a remainder of 0 beside a value that is not
    finite.  :func:`_unpack` adds them back in float64.

    On a TPU v5e the words added back give the very bits of the runtime's
    own float64 copy to the host: for every column of the kernel checked
    there, for +0 and the infinities.  The exceptions are tiny values,
    where a word that counts is subnormal (below about 2**-72 in
    magnitude; the device's float32 arithmetic flushes such words),
    -0.0, which comes back as +0.0, and the payload of a NaN.  A
    bit-cast to ``uint32`` would have no exceptions, but the TPU's
    compiler has no emulation of a 64-bit bit-cast."""
    hi = x.astype(jnp.float32)
    lo = (x - hi.astype(jnp.float64)).astype(jnp.float32)
    return hi, jnp.where(jnp.isfinite(hi), lo, 0.0)


def _unpack(host: np.ndarray, length: int) -> np.ndarray:
    """The ``(6, length)`` float64 columns of a fetched
    :func:`_columns_jax` buffer over ``length`` scenarios: the float64
    stack as it is, or the float32 words of its blocks added in float64
    and the blocks put back in order."""
    if host.dtype == np.float64:
        return host
    n, shards = len(_NUMERIC_COLS), host.shape[0]
    m = length // shards
    w = host.reshape(shards, 2 * n, -1)[:, :, :m]
    cols = w[:, :n].astype(np.float64) + w[:, n:]
    return cols.transpose(1, 0, 2).reshape(n, length)


@functools.cache
def _f64_words() -> bool:
    """True where the default device emulates float64 as two float32
    words (the TPU), so that :func:`_columns_jax` packs those words."""
    return jax.devices()[0].platform == "tpu"


def _shards(codes: np.ndarray | jax.Array) -> int:
    """The devices the scenario axis is split over: one for host codes,
    else those of the sharding :func:`_shard_codes` gave them."""
    if isinstance(codes, jax.Array):
        return len(codes.sharding.device_set)
    return 1


def _host_columns(args: tuple, size: int) -> dict[str, np.ndarray]:
    """One call of :func:`_columns_jax` on ``args``, as the first ``size``
    rows of each numeric column in host float64 arrays: views of one
    ``(6, S)`` host array, fetched in one transfer.

    What crosses to the device per call is two host buffers
    (:func:`_packed_args`): the float tables as one float64 buffer, the
    flags and integer codes as one int32 buffer.  Each transfer has a
    fixed cost, paid once per leaf otherwise (62 leaves for the
    frontier grid); and the codes, most of the bytes, are int32 because
    the TPU has no native 64-bit integer (it emulates one as a pair of
    32-bit words) while int32 holds every code exactly.  Codes already
    on the device (a mesh's) stay there.

    The device path splits into three spans: ``sweep.columns.call``
    (packing the two buffers, placing them on the device, the launch,
    and the start of the copy of the packed output to the host),
    ``sweep.columns.wait`` (the device work the host cannot hide,
    waited for only while the recorder is on) and
    ``sweep.columns.fetch`` (the end of the copy and the columns taken
    from it; with the recorder off it also does the waiting).  The
    counters ``sweep.h2d_arrays``/``sweep.h2d_bytes`` count the host
    buffers the call places and their bytes, and
    ``sweep.ep_h2d_bytes`` the bytes of the expert-parallel tables and
    codes in them (:func:`_host_h2d`); ``sweep.d2h_arrays``/
    ``sweep.d2h_bytes`` the device arrays and bytes the fetch brings to
    the host."""
    with obs.span("sweep.columns"), jax.enable_x64(True):
        pi = args[3]["pi"]
        shards = _shards(pi) if _f64_words() else 0
        with obs.span("sweep.columns.call"):
            packed = _packed_args(args)
            out = _columns_jax(*packed, shards=shards)
            out.copy_to_host_async()
        if obs.enabled():
            _host_h2d(packed[0], packed[1], packed[3])
            with obs.span("sweep.columns.wait"):
                out.block_until_ready()
        with obs.span("sweep.columns.fetch"):
            cols = _unpack(np.asarray(out), len(pi))
            obs.count("sweep.d2h_arrays")
            obs.count("sweep.d2h_bytes", out.nbytes)
            return {k: cols[i, :size] for i, k in enumerate(_NUMERIC_COLS)}


def _count_points(kernel_points: int, ep_points: int) -> None:
    """Counters ``sweep.kernel_points`` and ``sweep.ep_points``: the
    kernel points of one evaluation, and those with ``ep > 1``."""
    obs.count("sweep.kernel_points", kernel_points)
    obs.count("sweep.ep_points", ep_points)


# ----------------------------------------------------------------------
# Sharding: pad the batch axes to a device-count multiple and place
# the code vectors over the mesh's data axis.
# ----------------------------------------------------------------------
#: Benign fill for padding rows (index 0 is always valid; n=1 is the
#: zero-comm degenerate; batch=0 means "table default").
_PAD_FILL = {"n": 1, "ep": 1}


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
        pax = ev.points.pax
        if not ev.all_batched:
            bad = [name for name, f, t in zip(
                pax.names, pax.has_fast, pax.has_tl)
                if not (bool(f) or bool(t))]
            raise ValueError(
                f"backend='jax' evaluates closed-form and bucket-timeline "
                f"policies only; {bad} need the event-driven simulator. "
                f"Use backend='numpy' for grids containing them.")
        self.ev = ev
        (self._tables, self._pflags, kcodes, self._ucodes,
         self._tl_overlaps, self._coll_codes) = _kernel_args(ev.points)
        self._points = _point_counts(ev.points)
        S = len(ev)
        if S:
            sc = ev.scenario_codes(0, S)
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
        _count_points(*self._points)
        return _host_columns(self._args(params), S)

    def _args(self, params: dict | None = None) -> tuple:
        """The kernel's arguments, ``params`` swapped into the tables."""
        tables = self._tables
        if params:
            unknown = set(params) - set(PARAM_KEYS)
            if unknown:
                raise ValueError(f"unknown param keys {sorted(unknown)}; "
                                 f"differentiable params are {PARAM_KEYS}")
            tables = {**tables, **params}
        return (tables, self._pflags, self._kcodes, self._scodes,
                self._ucodes, self._tl_overlaps, self._coll_codes)

    def _traced_columns(self, params: dict | None = None) -> dict:
        """The numeric columns as a dict of device arrays, from the
        compiled :func:`_columns` — kept separate so the differentiable
        front end (:func:`iteration_time_fn`) can trace through it.
        Callers are responsible for the ``jax.enable_x64(True)`` scope."""
        return _columns_dict(*self._args(params))

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
        if ev.any_mc and len(ev):
            codes = ev.scenario_codes(0, len(ev))
            batched._apply_mc_tails(ev.points.take(codes["kidx"]), codes,
                                    ev.specs, cols, seed)
        else:
            t_iter = cols["iteration_time_s"]
            cols["t_mean_s"] = t_iter
            cols["t_p95_s"] = t_iter
            cols["t_p99_s"] = t_iter
        return JaxGridRun(self, cols)

    def method_labels(self, pi: np.ndarray) -> list[str]:
        """Per-row evaluation-path labels (``all_batched`` holds, so
        only the two batched labels occur)."""
        return METHOD_LABELS[self.ev.points.pax.tier[pi]].tolist()


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
            ev.scenario_codes(lo, hi)["pi"])
        return out

    def table_slice(self, lo: int, hi: int):
        """Columnar result table for flat scenario indices ``[lo, hi)``
        in grid order — the jax twin of
        :meth:`repro.core.batched.GridRun.table_slice` (the ``batched``
        mask is all-true by construction)."""
        ev = self._jev.ev
        codes = ev.scenario_codes(lo, hi)
        cols = {k: v[lo:hi] for k, v in self._cols.items()}
        cols["method_code"] = ev.points.pax.tier[codes["pi"]]
        return (batched.select_to_columns(cols, ev.label_columns(codes)),
                codes["batched"])

    def rows_slice(self, lo: int, hi: int) -> list[dict]:
        table, _ = self.table_slice(lo, hi)
        return rows_from_table(table)


def jax_grid_evaluator(grid: ScenarioGrid, *, mesh=None) -> JaxGridEvaluator:
    """Memoized :class:`JaxGridEvaluator`: the one held by ``grid``'s
    entry of the structure memo (:func:`repro.core.batched.grid_evaluator`),
    built on first use (unsharded/auto mesh only — explicit meshes always
    build fresh).  A build, the NumPy structure's own on a memo miss
    included, runs under the ``sweep.build`` span and counts 1 in
    ``sweep.builds``; a hit counts 0."""
    ev = None if mesh is not None else batched.cached_evaluator(grid)
    if ev is not None and ev.jax is not None:
        # a hit counts too, as no build: 0 builds differ from no counter
        obs.count("sweep.builds", 0)
        return ev.jax
    with obs.span("sweep.build"):
        obs.count("sweep.builds")
        jev = JaxGridEvaluator(grid, mesh=mesh)
    if mesh is None:
        jev.ev.jax = jev
    return jev


# ----------------------------------------------------------------------
# Scenario-list front end — jax twin of batched.eval_scenarios_table.
# ----------------------------------------------------------------------
def eval_scenarios_table_jax(
        scenarios: Sequence[Scenario] | Iterable[Scenario],
        seed: int = 0) -> dict[str, np.ndarray]:
    """Columnar result table (input order) for a list of
    batched-path-eligible scenarios, evaluated by the fused jit kernel
    with the identity scenario -> kernel-point map; the kernel points
    come from the shared :func:`repro.core.batched.scenario_points` pass
    and the straggler Monte Carlo tails from the shared host-side pass,
    exactly as on the grid path — which is what makes a
    *concatenation* of several queries' scenario lists bit-identical,
    column for column, to sweeping each query's grid directly (the
    sweep service's coalescer contract, pinned by
    ``tests/test_service.py``).  Raises ``ValueError`` (via
    :func:`repro.core.batched.scenario_points`) if any scenario's
    policy has neither a closed nor a bucket-timeline form."""
    from repro.core.resulttable import empty_table

    scenarios = list(scenarios)
    if not scenarios:
        return empty_table()
    points, codes, specs = batched.scenario_points(scenarios)
    tables, pflags, kcodes, ucodes, *static = _kernel_args(points)
    S = len(scenarios)
    scodes = {"pi": codes["pi"], "kidx": np.arange(S, dtype=np.int64)}
    _count_points(*_point_counts(points))
    cols = _host_columns((tables, pflags, kcodes, scodes, ucodes, *static),
                         S)
    batched._apply_mc_tails(points, codes, specs, cols, seed)
    cols["method_code"] = points.pax.tier[codes["pi"]]
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
    points = ev.points
    if params:
        link = {k: np.asarray(params[k], dtype=np.float64)
                for k in ("intra_bw", "intra_lat", "inter_bw", "inter_lat")
                if k in params}
        if link:
            points = dataclasses.replace(
                points, cax=dataclasses.replace(points.cax, **link))
        if "bucket_bytes" in params:
            bb = np.asarray(params["bucket_bytes"], dtype=np.float64)
            tl_specs = [(float(bb[i]), ov)
                        for i, (_, ov) in enumerate(points.pax.tl_specs)]
            points = dataclasses.replace(
                points, pax=dataclasses.replace(points.pax,
                                                tl_specs=tl_specs))
    kc = batched._kernel_cols(points)
    codes = ev.scenario_codes(0, len(ev))
    return batched._policy_select(points.pax, codes["pi"], kc,
                                  codes["kidx"])["iteration_time_s"]
