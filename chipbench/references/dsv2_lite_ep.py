"""Plain reference of DeepSeek-V2-Lite pre-training under data x expert
parallelism, one scenario at a time: the reference of the configuration
``dsv2_lite_ep``.

It exports what :mod:`chipbench.check` uses (``Reference(model, dtype)``
with ``.row``, ``scenario_at``, ``LABEL_COLUMNS``, ``NUMERIC_COLUMNS``).
Every number comes from the configuration's ``model`` section: the
published widths (``model["architecture"]``, the model's config.json),
the sequence and batch, the devices, links and policies.  It imports
nothing of the program; the link models and the pipeline steady state
are the frontier reference's (``references/frontier.py``, harness
code), reused as they are.

The deployment, written from the widths:

* layers: the embedding, ``first_k_dense_replace`` dense blocks, the
  MoE blocks, the untied head.  A block's parameters are its latent
  attention's four projections (``d*H*(nope+rope)``, ``d*(r+rope)``,
  ``r*H*(nope+v)``, ``H*v*d``) and its MLP: ``3*d*intermediate``, or
  ``n_routed_experts`` routed experts, ``n_shared_experts`` shared ones
  (``3*d*moe_intermediate`` each) and the router (``d*n_routed``);
  forward flops a sequence are twice the per-token-active parameters
  (``num_experts_per_tok`` routed experts) times the sequence, plus the
  causal attention term ``S*S*H*(nope+rope+v)`` in a block;
* expert parallelism of degree ``ep``: an EP group is ``ep`` contiguous
  ranks, on the intra-node link while ``ep`` fits in a node; each MoE
  layer runs four all-to-alls an iteration (dispatch and combine, in
  the forward and again in the backward), each moving
  ``batch*S*top_k*d*2`` bytes a device at ``(ep-1)/ep * bytes/bw +
  (ep-1) * latency``, in the compute chain; a layer's gradient is a
  dense all-reduce over all ``n`` ranks and, for the routed experts, an
  all-reduce of ``routed/ep`` bytes over the ``n/ep`` ranks that hold
  the same experts (``max(1, gpus_per_node // ep)`` a node), dense
  first on the one collective channel; fused buckets close on the
  per-device payload and are those two collectives; the update reads
  the per-device parameters.  ``ep = 1`` is plain data parallelism.

``dtype`` sets the precision of every operation: ``np.float64`` is the
reference, ``np.float32`` the control that a check must reject.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path

import numpy as np


def _frontier():
    path = Path(__file__).with_name("frontier.py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_references_frontier_for_dsv2_lite_ep", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


_base = _frontier()

NUMERIC_COLUMNS = _base.NUMERIC_COLUMNS
LABEL_COLUMNS = _base.LABEL_COLUMNS + ("ep_size",)


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
            "ep_size": int(picks["ep_sizes"]),
            "policy": picks["policies"], "collective": picks["collectives"],
            "interconnect": picks["interconnects"],
            "het": picks["het_profiles"], "straggler": picks["stragglers"]}


def layers(arch: dict, seq: int) -> list[dict]:
    """Per layer, in forward order: parameters, routed-expert
    parameters, forward flops a sequence and all-to-all elements a
    sequence (``S * top_k * d`` in a MoE block, 0 elsewhere)."""
    d, V = arch["hidden_size"], arch["vocab_size"]
    H, r = arch["num_attention_heads"], arch["kv_lora_rank"]
    nope, rope = arch["qk_nope_head_dim"], arch["qk_rope_head_dim"]
    v = arch["v_head_dim"]
    E, k = arch["n_routed_experts"], arch["num_experts_per_tok"]
    moe_ff = arch["moe_intermediate_size"]
    attn = d * H * (nope + rope) + d * (r + rope) + r * H * (nope + v) \
        + H * v * d
    scores = seq * seq * H * (nope + rope + v)
    out = [{"params": V * d, "routed": 0, "flops": 2.0 * V * d * seq,
            "a2a": 0}]
    for i in range(arch["num_hidden_layers"]):
        if i < arch["first_k_dense_replace"]:
            mlp = 3 * d * arch["intermediate_size"]
            out.append({"params": attn + mlp, "routed": 0,
                        "flops": 2.0 * (attn + mlp) * seq + scores,
                        "a2a": 0})
            continue
        routed = E * 3 * d * moe_ff
        rest = arch["n_shared_experts"] * 3 * d * moe_ff + d * E
        active = attn + k * 3 * d * moe_ff + rest
        out.append({"params": attn + routed + rest, "routed": routed,
                    "flops": 2.0 * active * seq + scores,
                    "a2a": seq * k * d})
    if not arch["tie_word_embeddings"]:
        out.append({"params": d * V, "routed": 0,
                    "flops": 2.0 * d * V * seq, "a2a": 0})
    return out


class Reference(_base.Reference):
    """Evaluates scenarios of the configuration's ``model`` in
    ``dtype``."""

    def _layers(self):
        if "layers" not in self._tables:
            m = self.model
            self._tables["layers"] = layers(m["architecture"], m["seq_len"])
        return self._tables["layers"]

    def alltoall(self, nbytes, ep, gpn, intra, inter):
        """Seconds of one all-to-all of ``nbytes`` a rank over ``ep``
        contiguous ranks."""
        f = self.f
        if ep <= 1:
            return f(0.0)
        bw, lat = intra if ep <= gpn else inter
        e = f(ep)
        return (e - f(1)) / e * nbytes / bw + (e - f(1)) * lat

    def row(self, s: dict) -> dict:
        """The result row of scenario ``s`` (keys: :data:`LABEL_COLUMNS`
        and :data:`NUMERIC_COLUMNS`)."""
        if s["het"] is not None or s["straggler"] is not None:
            raise ValueError(f"heterogeneous workers and stragglers are not "
                             f"modelled: {s['het']!r}, {s['straggler']!r}")
        f, m = self.f, self.model
        gpn, intra, inter, disk, h2d, dev = self._links(s)
        policy = m["policies"][s["policy"]]
        n, ep, coll = s["n_workers"], s["ep_size"], s["collective"]
        batch, seq = m["batch_per_gpu"], m["seq_len"]
        gb = f(m["grad_bytes_per_param"])
        rate = f(dev["peak_flops"]) * f(dev["compute_efficiency"])
        group, group_gpn = n // ep, max(1, gpn // ep)

        t_f, t_b, dense, expert, t_c, params = [], [], [], [], [], f(0.0)
        for layer in self._layers():
            fwd = f(layer["flops"]) * f(batch) / rate
            a2a = self.alltoall(
                f(batch) * f(layer["a2a"]) * f(m["activation_bytes"]),
                ep, gpn, intra, inter) if layer["a2a"] else f(0.0)
            t_f.append(fwd + f(2.0) * a2a)
            t_b.append(f(m["bwd_fwd_ratio"]) * fwd + f(2.0) * a2a)
            routed = gb * f(layer["routed"]) if ep > 1 else f(0.0)
            d_bytes = gb * f(layer["params"]) - routed
            e_bytes = routed / f(ep)
            dense.append(d_bytes)
            expert.append(e_bytes)
            params = params + d_bytes + e_bytes
            t_c.append(self._pair(d_bytes, e_bytes, n, group, gpn,
                                  group_gpn, intra, inter, coll))

        nbytes_in = f(batch) * f(seq) * f(m["input_bytes_per_token"])
        t_io = disk[1] + nbytes_in / disk[0]
        t_h2d = h2d[1] + nbytes_in / h2d[0]
        t_u = f(m["update_traffic"]) * params / f(dev["hbm_bandwidth"])
        payload = [a + b for a, b in zip(dense, expert)]
        fused = policy.get("bucket_bytes") is not None \
            or policy.get("per_layer_queue", False)
        if fused:
            buckets = self._buckets(payload, policy)
            durations = []
            for members in buckets:
                d_sum, e_sum = f(0.0), f(0.0)
                for layer in members:
                    d_sum = d_sum + dense[layer]
                    e_sum = e_sum + expert[layer]
                durations.append(self._pair(d_sum, e_sum, n, group, gpn,
                                            group_gpn, intra, inter, coll))
        else:
            buckets = [[l] for l in range(len(payload) - 1, -1, -1)
                       if payload[l] > 0]
            durations = [t_c[b[0]] for b in buckets]

        t = self._iteration(t_f, t_b, buckets, durations, t_io, t_h2d,
                            t_u, policy)
        t1 = self._iteration(t_f, t_b, buckets, durations, t_io, t_h2d,
                             t_u, policy, with_comm=False)
        t_comm, t_comp = f(0.0), f(0.0)
        for x in t_c:
            t_comm = t_comm + x
        for x in t_f + t_b:
            t_comp = t_comp + x
        return {
            "workload": s["workload"], "cluster": s["cluster"],
            "n_workers": n, "ep_size": ep, "policy": s["policy"],
            "collective": coll,
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

    def _pair(self, d_bytes, e_bytes, n, group, gpn, group_gpn, intra,
              inter, coll):
        """The dense all-reduce over ``n`` ranks and the expert one over
        the ``group`` ranks that hold the same experts, back to back."""
        f = self.f
        t = self.allreduce(d_bytes, n, gpn, intra, inter, coll) \
            if d_bytes > 0 else f(0.0)
        if e_bytes > 0:
            t = t + self.allreduce(e_bytes, group, group_gpn, intra, inter,
                                   coll)
        return t
