"""Plain reference of the DAG model of S-SGD, one scenario at a time:
the reference of the configuration ``frontier``.

A configuration's reference is ``chipbench/references/<config>.py``,
found by the configuration's name (``chipbench/run.py:resolve``), and
exports the four names :func:`chipbench.check.compare` uses:
``Reference(model, dtype)`` with ``.row(scenario)``,
``scenario_at(axes, i)``, ``LABEL_COLUMNS`` and ``NUMERIC_COLUMNS``.

Written from the paper (arXiv:1805.03812, Eqs. 1-6 and the Fig. 1 DAG)
and the configuration files under ``chipbench/configs``; it imports
nothing of the program under test and reads none of its tables.  Every
number it needs (layer shapes, devices, links, policy flags) comes from
the configuration's ``model`` section.

A scenario is evaluated as the DAG's steady state, written as plain
loops over layers and gradient buckets:

* per-layer forward/backward times from forward FLOPs at the device's
  achieved rate (backward = ``bwd_fwd_ratio`` x forward);
* per-layer all-reduce times from alpha-beta link models (ring, double
  binary tree, two-level hierarchical);
* the communication the GPU chain cannot hide, by running the single
  collective channel in issue order: a bucket (one layer, or a fused
  group of layers for the bucketed policies) starts when the channel
  is free and its last gradient is ready;
* the pipeline equations: blocking I/O, overlapped I/O with an early or
  a late host-to-device copy.

Scenarios run on equal workers without stragglers, as the paper's do:
the tail columns repeat the iteration time, and a heterogeneity profile
or a straggler spec is refused.

``dtype`` sets the precision of every operation: ``np.float64`` is the
reference, ``np.float32`` the control that a check must reject.
"""
from __future__ import annotations

import math

import numpy as np

NUMERIC_COLUMNS = ("iteration_time_s", "samples_per_sec", "speedup",
                   "t_comm_s", "t_comp_s", "t_mean_s", "t_p95_s", "t_p99_s")
LABEL_COLUMNS = ("workload", "cluster", "n_workers", "policy", "collective",
                 "interconnect", "het", "straggler", "sync_k", "faults",
                 "batch_per_gpu", "method")


# ----------------------------------------------------------------------
# Layer tables from the published architectures.
# ----------------------------------------------------------------------
def _conv(h, w, cout, k, cin, groups=1):
    """(forward flop/sample, parameters) of a conv layer with bias."""
    cin_g = cin // groups
    return 2.0 * h * w * cout * k * k * cin_g, cout * k * k * cin_g + cout


def _fc(nin, nout):
    return 2.0 * nin * nout, nin * nout + nout


def layer_table(spec: dict) -> tuple[list[float], list[int]]:
    """Per-layer forward flop/sample and parameter counts, in forward
    order, for one workload of the configuration."""
    flops, params = [], []

    def add(fp):
        flops.append(float(fp[0]))
        params.append(int(fp[1]))

    if spec["arch"] == "plain":
        for layer in spec["layers"]:
            if "conv" in layer:
                add(_conv(*layer["conv"]))
            elif "fc" in layer:
                add(_fc(*layer["fc"]))
            else:
                add((sum(math.prod(dims) for dims in layer["act"]), 0))
    elif spec["arch"] == "bottleneck":
        add(_conv(*spec["stem"]))
        for blocks, cin, mid, cout, hw in spec["stages"]:
            for b in range(blocks):
                cin_b = cin if b == 0 else cout
                add(_conv(hw, hw, mid, 1, cin_b))
                add(_conv(hw, hw, mid, 3, mid))
                add(_conv(hw, hw, cout, 1, mid))
                if b == 0:
                    add(_conv(hw, hw, cout, 1, cin_b))
                add((3 * hw * hw * cout, 0))
        add(_fc(*spec["head"]))
    elif spec["arch"] == "inception":
        for stem in spec["stem"]:
            add(_conv(*stem))
        for hw, cin, c1, c3r, c3, c5r, c5, cp in spec["inception"]:
            parts = (_conv(hw, hw, c1, 1, cin), _conv(hw, hw, c3r, 1, cin),
                     _conv(hw, hw, c3, 3, c3r), _conv(hw, hw, c5r, 1, cin),
                     _conv(hw, hw, c5, 5, c5r), _conv(hw, hw, cp, 1, cin))
            add((sum(p[0] for p in parts), sum(p[1] for p in parts)))
        add(_fc(*spec["head"]))
    else:
        raise ValueError(f"unknown architecture kind {spec['arch']!r}")
    return flops, params


# ----------------------------------------------------------------------
# Scenario grid: the row order of a sweep's result.
# ----------------------------------------------------------------------
def scenario_at(axes: dict, i: int) -> dict:
    """The scenario at row ``i`` of a grid whose axes are given in
    ``axes["axis_order"]`` order, the last axis varying fastest."""
    order = axes["axis_order"]
    picks = {}
    for name in reversed(order):
        values = axes[name]
        i, k = divmod(i, len(values))
        picks[name] = values[k]
    if i:
        raise IndexError("row index beyond the grid")
    return {"workload": picks["workloads"], "cluster": picks["clusters"],
            "n_workers": int(picks["worker_counts"]),
            "policy": picks["policies"], "collective": picks["collectives"],
            "interconnect": picks["interconnects"],
            "het": picks["het_profiles"], "straggler": picks["stragglers"]}


# ----------------------------------------------------------------------
# The reference evaluator.
# ----------------------------------------------------------------------
class Reference:
    """Evaluates scenarios of one configuration's ``model`` in ``dtype``."""

    def __init__(self, model: dict, dtype=np.float64):
        self.model = model
        self.f = dtype
        self._tables: dict = {}

    # -- inputs ----------------------------------------------------------
    def _workload(self, name: str):
        if name not in self._tables:
            spec = self.model["workloads"][name]
            flops, params = layer_table(spec)
            gbp = self.model["grad_bytes_per_param"]
            self._tables[name] = (
                np.array(flops, dtype=self.f),
                np.array([gbp * p for p in params], dtype=self.f),
                self.f(gbp * sum(params)), spec)
        return self._tables[name]

    def _links(self, s: dict):
        """``(gpus_per_node, intra, inter, disk, h2d, device)``; each
        link is ``(effective bandwidth, latency)``."""
        f = self.f
        cl = self.model["clusters"][s["cluster"]]
        intra_bw, intra_lat, intra_eff = cl["intra"]
        inter_bw, inter_lat, inter_eff = cl["inter"]
        ic = s["interconnect"]
        if ic not in (None, "default"):
            base, *mods = ic.split("@")
            inter_bw, inter_lat, inter_eff = self.model["inter_links"][base]
            for mod in mods:
                if mod.startswith("bw"):
                    inter_bw = inter_bw * float(mod[2:])
                else:
                    inter_lat = inter_lat * float(mod[3:])
        intra = (f(intra_bw) * f(intra_eff), f(intra_lat))
        inter = (f(inter_bw) * f(inter_eff), f(inter_lat))
        disk = (f(cl["disk"][0]) * f(cl["disk"][2]), f(cl["disk"][1]))
        h2d = (f(cl["h2d"][0]) * f(cl["h2d"][2]), f(cl["h2d"][1]))
        dev = self.model["devices"][cl["device"]]
        return cl["gpus_per_node"], intra, inter, disk, h2d, dev

    # -- collectives -----------------------------------------------------
    def allreduce(self, nbytes, n, gpn, intra, inter, algorithm):
        """Seconds to all-reduce ``nbytes`` per rank over ``n`` ranks."""
        f = self.f
        if n <= 1:
            return f(0.0)
        nf = f(n)
        if algorithm in ("ring", "tree"):
            bw, lat = intra if n <= gpn else inter
            if algorithm == "ring":
                return f(2.0) * (nf - f(1)) / nf * nbytes / bw \
                    + f(2.0) * (nf - f(1)) * lat
            depth = f(math.ceil(math.log2(n)))
            return f(2.0) * nbytes / bw + f(2.0) * depth * lat
        g = min(n, gpn)
        nodes = -(-n // g)
        gf = f(g)
        t = f(0.0)
        if g > 1:
            t = f(2.0) * ((gf - f(1)) / gf * nbytes / intra[0]
                          + (gf - f(1)) * intra[1])
        if nodes > 1:
            nn = f(nodes)
            t = t + f(2.0) * (nn - f(1)) / nn * (nbytes / gf) / inter[0] \
                + f(2.0) * (nn - f(1)) * inter[1]
        return t

    # -- the steady state --------------------------------------------------
    def _buckets(self, grad, policy: dict) -> list[list[int]]:
        """Gradient buckets in issue order: layers visited last to first,
        layers without gradients skipped, a bucket closed once its bytes
        reach the policy's bucket size (one layer per bucket without
        fusion)."""
        size = policy.get("bucket_bytes")
        buckets, cur, cur_bytes = [], [], 0.0
        for layer in range(len(grad) - 1, -1, -1):
            if not grad[layer] > 0:
                continue
            cur.append(layer)
            cur_bytes += float(grad[layer])
            if size is None or cur_bytes >= size:
                buckets.append(cur)
                cur, cur_bytes = [], 0.0
        if cur:
            buckets.append(cur)
        return buckets

    def _hidden_residual(self, t_b, buckets, durations):
        """Communication left after the backward pass: the collective
        channel runs buckets in issue order, each once the channel is
        free and its earliest layer's backward has finished."""
        f = self.f
        total_b = f(0.0)
        done_at = {}
        for layer in range(len(t_b) - 1, -1, -1):
            total_b = total_b + t_b[layer]
            done_at[layer] = total_b
        channel = f(0.0)
        for members, d in zip(buckets, durations):
            channel = np.maximum(channel, done_at[members[-1]]) + d
        return np.maximum(channel - total_b, f(0.0))

    def _iteration(self, t_f, t_b, buckets, durations, t_io, t_h2d, t_u,
                   policy: dict, with_comm: bool = True):
        """Steady-state iteration time of the pipeline."""
        f = self.f
        comp = f(0.0)
        for x in t_f:
            comp = comp + x
        for x in t_b:
            comp = comp + x
        comm = f(0.0)
        if with_comm and policy["wfbp"]:
            comm = self._hidden_residual(t_b, buckets, durations)
        elif with_comm:
            for d in durations:
                comm = comm + d
        chain = comp + comm + t_u
        if not policy["overlap_io"]:
            return t_io + t_h2d + chain
        if policy["h2d_early"]:
            return np.maximum(t_io + t_h2d, chain)
        return np.maximum(t_io + t_h2d, t_h2d + chain)

    def row(self, s: dict) -> dict:
        """The result row of scenario ``s`` (keys: :data:`LABEL_COLUMNS`
        and :data:`NUMERIC_COLUMNS`)."""
        if s["het"] is not None or s["straggler"] is not None:
            raise ValueError(f"heterogeneous workers and stragglers are not "
                             f"modelled: {s['het']!r}, {s['straggler']!r}")
        f = self.f
        flops, grad, param_bytes, spec = self._workload(s["workload"])
        gpn, intra, inter, disk, h2d, dev = self._links(s)
        policy = self.model["policies"][s["policy"]]
        n = s["n_workers"]
        batch = spec["batch_per_gpu"]
        rate = f(dev["peak_flops"]) * f(dev["compute_efficiency"])
        t_f0 = flops * f(batch) / rate
        t_b0 = f(self.model["bwd_fwd_ratio"]) * t_f0
        t_c = [self.allreduce(g, n, gpn, intra, inter, s["collective"])
               if g > 0 else f(0.0) for g in grad]
        nbytes_in = f(batch) * f(spec["bytes_per_sample"])
        t_io = disk[1] + nbytes_in / disk[0]
        t_h2d = h2d[1] + nbytes_in / h2d[0]
        t_u = f(self.model["update_traffic"]) * param_bytes \
            / f(dev["hbm_bandwidth"])
        fused = policy.get("bucket_bytes") is not None \
            or policy.get("per_layer_queue", False)
        if fused:
            buckets = self._buckets(grad, policy)
            durations = []
            for members in buckets:
                total = f(0.0)
                for layer in members:
                    total = total + grad[layer]
                durations.append(self.allreduce(total, n, gpn, intra, inter,
                                                s["collective"]))
        else:
            buckets = [[l] for l in range(len(grad) - 1, -1, -1)
                       if grad[l] > 0]
            durations = [t_c[m[0]] for m in buckets]

        t = self._iteration(t_f0, t_b0, buckets, durations, t_io, t_h2d,
                            t_u, policy)
        t1 = self._iteration(t_f0, t_b0, buckets, durations, t_io, t_h2d,
                             t_u, policy, with_comm=False)
        t_comm = f(0.0)
        for x in t_c:
            t_comm = t_comm + x
        t_comp = f(0.0)
        for x in t_f0:
            t_comp = t_comp + x
        for x in t_b0:
            t_comp = t_comp + x
        return {
            "workload": s["workload"], "cluster": s["cluster"],
            "n_workers": n, "policy": s["policy"],
            "collective": s["collective"],
            "interconnect": s["interconnect"] or "default",
            "het": "none", "straggler": "none",
            "sync_k": 0, "faults": "none", "batch_per_gpu": batch,
            "method": "timeline" if fused else "analytical",
            "iteration_time_s": float(t),
            "samples_per_sec": float(f(n) * f(batch) / t),
            "speedup": float(f(n) * t1 / t),
            "t_comm_s": float(t_comm), "t_comp_s": float(t_comp),
            "t_mean_s": float(t), "t_p95_s": float(t), "t_p99_s": float(t),
        }
