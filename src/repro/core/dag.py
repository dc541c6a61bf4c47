"""The paper's DAG model of S-SGD (Section IV).

A training job is a DAG ``G = (V_c U V_n, E)`` where ``V_c`` are
*computing* tasks (per-layer forward/backward, model update), ``V_n``
are *communication* tasks (disk I/O, host-to-device copy, per-layer
gradient aggregation), and a directed edge ``(x, y)`` means task ``y``
may only start after ``x`` finishes.

``build_ssgd_dag`` reproduces Fig. 1 of the paper for an arbitrary
number of layers, workers and iterations, parameterized by an overlap
:class:`~repro.core.policies.Policy` — which is exactly how the paper
distinguishes Caffe-MPI / CNTK / MXNet / TensorFlow.
"""
from __future__ import annotations

import dataclasses
import enum
from dataclasses import dataclass, field
from typing import Callable, Iterable, Sequence

import numpy as np

from repro.core.policies import Policy


class TaskKind(enum.Enum):
    COMPUTE = "compute"
    COMM = "comm"


# Channel name templates.  The simulator serializes tasks that share a
# channel; distinct channels run in parallel (GPU stream vs. PCIe vs.
# disk vs. the collective network, as in the paper's two task classes).
def gpu_channel(worker: int) -> str:
    return f"gpu:{worker}"


def disk_channel(worker: int) -> str:
    return f"disk:{worker}"


def pcie_channel(worker: int) -> str:
    return f"pcie:{worker}"


NET_CHANNEL = "net"

#: Expert-parallel all-to-all channel: MoE dispatch and combine, kept
#: apart from the gradient ``net`` channel (see :class:`IterationCosts`).
EP_CHANNEL = "ep"

#: Shared checkpoint-store channel: crash restores read the same npz
#: store (:mod:`repro.checkpoint.ckpt`), so they serialize — which is
#: what makes the per-iteration fault penalty additive in the crash
#: count (see :class:`repro.core.het.FaultSpec`).
CKPT_CHANNEL = "ckpt"


@dataclass
class Task:
    tid: int
    name: str
    kind: TaskKind
    duration: float
    channel: str
    iteration: int = 0
    layer: int | None = None          # 1-based, as in the paper
    worker: int | None = None
    priority: float = 0.0             # lower = scheduled first on channel ties
    nbytes: float = 0.0               # payload for comm tasks


@dataclass
class DAG:
    """Directed acyclic graph of :class:`Task` with precedence edges."""

    tasks: dict[int, Task] = field(default_factory=dict)
    preds: dict[int, set[int]] = field(default_factory=dict)
    succs: dict[int, set[int]] = field(default_factory=dict)
    _next_id: int = 0

    # -- construction ---------------------------------------------------
    def add_task(self, name: str, kind: TaskKind, duration: float, channel: str,
                 **kw) -> int:
        if duration < 0:
            raise ValueError(f"negative duration for task {name}: {duration}")
        tid = self._next_id
        self._next_id += 1
        self.tasks[tid] = Task(tid, name, kind, float(duration), channel, **kw)
        self.preds[tid] = set()
        self.succs[tid] = set()
        return tid

    def add_edge(self, src: int, dst: int) -> None:
        if src == dst:
            raise ValueError("self edge")
        self.preds[dst].add(src)
        self.succs[src].add(dst)

    def add_edges(self, srcs: Iterable[int], dst: int) -> None:
        for s in srcs:
            self.add_edge(s, dst)

    # -- queries ----------------------------------------------------------
    def __len__(self) -> int:
        return len(self.tasks)

    def sources(self) -> list[int]:
        return [t for t in self.tasks if not self.preds[t]]

    def sinks(self) -> list[int]:
        return [t for t in self.tasks if not self.succs[t]]

    def topo_order(self) -> list[int]:
        """Kahn topological order; raises if the graph has a cycle."""
        indeg = {t: len(p) for t, p in self.preds.items()}
        ready = sorted([t for t, d in indeg.items() if d == 0])
        order: list[int] = []
        import heapq

        heapq.heapify(ready)
        while ready:
            t = heapq.heappop(ready)
            order.append(t)
            for s in self.succs[t]:
                indeg[s] -= 1
                if indeg[s] == 0:
                    heapq.heappush(ready, s)
        if len(order) != len(self.tasks):
            raise ValueError("DAG contains a cycle")
        return order

    def critical_path(self) -> tuple[float, list[int]]:
        """Makespan with infinite resources (longest path)."""
        finish: dict[int, float] = {}
        best_pred: dict[int, int | None] = {}
        for t in self.topo_order():
            start = 0.0
            bp = None
            for p in self.preds[t]:
                if finish[p] > start:
                    start, bp = finish[p], p
            finish[t] = start + self.tasks[t].duration
            best_pred[t] = bp
        end = max(finish, key=lambda t: finish[t])
        path = [end]
        while best_pred[path[-1]] is not None:
            path.append(best_pred[path[-1]])  # type: ignore[arg-type]
        return finish[end], list(reversed(path))

    def total_work(self) -> float:
        return sum(t.duration for t in self.tasks.values())


@dataclass(frozen=True)
class IterationCosts:
    """Per-iteration task durations feeding the DAG builder.

    This is the paper's Table I vocabulary: ``t_io``, ``t_h2d``,
    layer-wise ``t_f^(l)``, ``t_b^(l)``, ``t_c^(l)`` and ``t_u``.
    Comm durations are for the *collective* across all participating
    workers (layer-wise all-reduce), as measured in the paper's traces.

    Expert parallelism (``t_a2a`` set) adds, per layer: ``t_a2a``, one
    all-to-all of the layer's MoE dispatch or combine (0 on a layer
    without routed experts), of which the layer runs four an iteration
    — dispatch and combine after its forward, their gradients before
    its backward — each one task on :data:`EP_CHANNEL` that waits for
    every worker and that every worker waits for; and ``t_ce``, the
    all-reduce of the routed experts' gradient (per-device payload
    ``expert_bytes``) over the ranks that hold the same experts, issued
    right after the layer's dense all-reduce ``t_c`` on the ``net``
    channel.  The EP channel is a departure: real all-to-alls share
    links with the gradient traffic.
    """

    t_f: Sequence[float]              # forward, layer 1..L
    t_b: Sequence[float]              # backward, layer 1..L (index 0 = layer 1)
    t_c: Sequence[float]              # gradient all-reduce, layer 1..L
    t_io: float = 0.0
    t_h2d: float = 0.0
    t_u: float = 0.0
    grad_bytes: Sequence[float] | None = None   # per layer, for bucketing
    t_a2a: Sequence[float] | None = None        # one all-to-all, per layer
    t_ce: Sequence[float] | None = None         # expert-gradient all-reduce
    expert_bytes: Sequence[float] | None = None  # its per-device payload

    @property
    def num_layers(self) -> int:
        return len(self.t_f)

    def folded(self) -> "IterationCosts":
        """The costs as the closed forms read them: each layer's four
        all-to-alls inside its compute (``t_f + 2 t_a2a``, ``t_b + 2
        t_a2a``) and its two all-reduces as one comm term (``t_c +
        t_ce``).  Exact for the steady state: the all-to-alls sit on the
        compute chain, and the two all-reduces share a release time and
        run back to back on the one ``net`` channel.  Costs without
        expert parallelism come back as they are."""
        if self.t_a2a is None:
            return self
        a2a = 2.0 * np.asarray(self.t_a2a)
        return dataclasses.replace(
            self, t_f=np.asarray(self.t_f) + a2a,
            t_b=np.asarray(self.t_b) + a2a,
            t_c=np.asarray(self.t_c) + np.asarray(self.t_ce),
            grad_bytes=None if self.grad_bytes is None
            else np.asarray(self.grad_bytes) + np.asarray(self.expert_bytes),
            t_a2a=None, t_ce=None, expert_bytes=None)

    def with_comm(self, t_c: Sequence[float],
                  grad_bytes: Sequence[float] | None = None) -> "IterationCosts":
        """Copy with the per-layer comm durations replaced — used by the
        sweep engine to re-cost the same compute profile under a
        different collective algorithm / interconnect without rebuilding
        the layer tables."""
        return dataclasses.replace(
            self, t_c=list(t_c),
            grad_bytes=self.grad_bytes if grad_bytes is None else list(grad_bytes))

    def __post_init__(self):
        if not (len(self.t_f) == len(self.t_b) == len(self.t_c)):
            raise ValueError("t_f, t_b, t_c must have equal length")
        if self.grad_bytes is not None and len(self.grad_bytes) != len(self.t_f):
            raise ValueError("grad_bytes length mismatch")
        ep = [x is None for x in (self.t_a2a, self.t_ce, self.expert_bytes)]
        if any(ep) and not all(ep):
            raise ValueError("t_a2a, t_ce and expert_bytes come together")
        if not any(ep) and not (len(self.t_a2a) == len(self.t_ce)
                                == len(self.expert_bytes) == len(self.t_f)):
            raise ValueError("t_a2a, t_ce, expert_bytes length mismatch")


def _bucketize(costs: IterationCosts, policy: Policy,
               comm_scale: Callable[[float, float], float] | None) -> list[tuple[str, list[int], float]]:
    """Group layers (in backward order L..1) into communication buckets.

    Returns ``[(name, member_layers, duration)]`` in issue order.  With
    ``policy.bucket_bytes`` unset every learnable layer is its own
    bucket (the paper's layer-wise NCCL pattern).  With bucketing the
    durations are re-derived via ``comm_scale(total_bytes, total_time)``
    when byte sizes are known, else summed.

    Under expert parallelism (``costs.t_ce`` set) a layer has
    communication if it has gradient bytes at all, buckets close on the
    per-device payload ``grad_bytes + expert_bytes``, and ``duration``
    is the bucket's dense all-reduce; :func:`_expert_duration` gives its
    expert one.

    Boundaries come from the shared
    :func:`repro.core.bucketsim.bucket_partition` — the one boundary
    rule this builder and the batched timeline kernel both consume, so
    the event-driven oracle and the batched path can never drift.
    """
    from repro.core.bucketsim import bucket_partition  # circular-safe

    mask, payload = [c > 0 for c in costs.t_c], costs.grad_bytes
    if costs.t_ce is not None:
        mask = [c + e > 0 for c, e in zip(costs.t_c, costs.t_ce)]
        if payload is not None:
            payload = [g + e for g, e in zip(payload, costs.expert_bytes)]
            mask = [p > 0 for p in payload]
    if not policy.bucket_bytes:
        return [(f"comm_l{m + 1}", [m], costs.t_c[m])
                for [m] in bucket_partition(mask, None, None)]

    buckets: list[tuple[str, list[int], float]] = []
    for members in bucket_partition(mask, payload, policy.bucket_bytes):
        buckets.append((f"comm_bucket{len(buckets)}", members,
                        _fused(comm_scale, costs.grad_bytes, costs.t_c,
                               members)))
    return buckets


def _fused(scale, nbytes, times, members) -> float:
    """One collective over the bucket ``members``: ``scale(total_bytes,
    total_time)`` when byte sizes are known, else the summed times."""
    cur_time = sum(times[m] for m in members)
    cur_bytes = sum(nbytes[m] for m in members) if nbytes is not None \
        else 0.0
    return scale(cur_bytes, cur_time) if (scale and cur_bytes) else cur_time


def _expert_duration(costs: IterationCosts, members: list[int],
                     policy: Policy,
                     scale: Callable[[float, float], float] | None) -> float:
    """The expert all-reduce of a bucket from :func:`_bucketize`: its
    layers' ``t_ce`` without fusion, else one collective over their
    ``expert_bytes`` (``scale`` costs it over the expert group)."""
    if not policy.bucket_bytes:
        return costs.t_ce[members[0]]
    return _fused(scale, costs.expert_bytes, costs.t_ce, members)


class SSGDDagBuilder:
    """Incremental Fig.-1 DAG construction, one iteration at a time.

    Holds the cross-iteration state (the previous update and H2D
    tasks) so callers can interleave :meth:`add_iteration` with
    incremental simulation — this is what lets
    :func:`repro.core.simulator.simulate_steady` stop building as soon
    as the update-task deltas converge instead of always paying the
    full warm-up cap.  :func:`build_ssgd_dag` wraps it for the common
    build-everything-up-front case.
    """

    def __init__(self, costs: IterationCosts, n_workers: int, policy: Policy,
                 comm_scale: Callable[[float, float], float] | None = None,
                 expert_comm_scale: Callable[[float, float], float] | None = None,
                 shared_compute: bool = False,
                 worker_scale: Sequence[float] | None = None,
                 sync_k: int | None = None,
                 crashed: Sequence[int] = (),
                 restart_s: float = 0.0):
        if n_workers < 1:
            raise ValueError("n_workers >= 1")
        if restart_s < 0:
            raise ValueError("restart_s must be >= 0")
        if worker_scale is not None:
            worker_scale = [float(s) for s in worker_scale]
            if len(worker_scale) != n_workers:
                raise ValueError(
                    f"worker_scale must have one entry per worker "
                    f"({n_workers}), got {len(worker_scale)}")
            if any(s <= 0 for s in worker_scale):
                raise ValueError("worker_scale entries must be > 0")
        self.dag = DAG()
        self.costs = costs
        self.n_workers = n_workers
        self.policy = policy
        self.n_iterations = 0
        # Per-worker compute-time multipliers (heterogeneous GPUs /
        # straggler jitter): worker ``w``'s forward and backward tasks
        # run ``worker_scale[w]`` x slower.  I/O, H2D, comm and the
        # update are deliberately unscaled — they live on their own
        # channels (disk/PCIe/net) or are HBM-bound (t_u).
        self._worker_scale = worker_scale
        # ``shared_compute`` serializes all workers on one compute
        # channel — models host-device oversubscription (N logical
        # devices on one core), used by examples/dag_validation.py.
        self._gpu_of = (lambda w: "gpu:shared") if shared_compute \
            else gpu_channel
        # bucket boundaries depend only on (costs, policy, comm_scale)
        self._buckets = _bucketize(costs, policy, comm_scale) \
            if n_workers > 1 else []
        self._expert_durs = None if costs.t_ce is None else [
            _expert_duration(costs, members, policy, expert_comm_scale)
            for _, members, _ in self._buckets]
        # all-to-all layers (expert parallelism): each is a barrier
        # across every worker, so K-of-N partial sync cannot skip one
        self._a2a = [] if costs.t_a2a is None \
            else [l for l, t in enumerate(costs.t_a2a) if t > 0]
        if self._a2a and sync_k and int(sync_k) < n_workers:
            raise ValueError("K-of-N partial sync with all-to-alls: every "
                             "all-to-all waits for all workers")
        # K-of-N partial synchronization: the aggregation and the model
        # update gate on the K *fastest* workers only (smallest
        # compute multiplier, ties broken by worker index — exactly the
        # K-th order statistic the closed form takes).  ``None`` keeps
        # the full-sync edge set bit-identical to the historical path.
        keff = n_workers if not sync_k or int(sync_k) <= 0 \
            else min(int(sync_k), n_workers)
        if keff < n_workers:
            ws = worker_scale if worker_scale is not None \
                else [1.0] * n_workers
            order = sorted(range(n_workers), key=lambda w: (ws[w], w))
            self._sync_workers: list[int] | None = sorted(order[:keff])
        else:
            self._sync_workers = None
        # Crash/recover events: each worker in ``crashed`` loses its
        # state every iteration and re-reads the checkpoint
        # (``restart_s`` seconds on the shared CKPT_CHANNEL) before the
        # model update may broadcast.
        self._crashed = sorted({int(w) for w in crashed})
        if any(w < 0 or w >= n_workers for w in self._crashed):
            raise ValueError("crashed worker index out of range")
        self._restart_s = float(restart_s)
        self._prev_update: int | None = None
        self._prev_h2d: list[int] = []

    def add_iteration(self) -> int:
        """Append one iteration's tasks and edges; returns the
        iteration's ``update`` task id."""
        g, costs, policy = self.dag, self.costs, self.policy
        L = costs.num_layers
        it = self.n_iterations
        prev_update, prev_h2d = self._prev_update, self._prev_h2d

        # --- I/O + H2D (communication tasks T0-T7 in Fig. 1) -----------
        h2d_tasks = []
        for w in range(self.n_workers):
            io = g.add_task(f"io_w{w}", TaskKind.COMM, costs.t_io,
                            disk_channel(w), iteration=it, worker=w)
            # Overlapped I/O: next fetch only waits for the previous fetch
            # (disk channel); otherwise it waits for the previous update.
            if prev_update is not None and not policy.overlap_io:
                g.add_edge(prev_update, io)
            if prev_h2d:
                # Single staging buffer: the next fetch reuses the buffer
                # freed by the previous upload, so the prefetch stage has
                # period t_io + t_h2d — exactly the paper's Eq. (3)/(5)
                # term max(t_io + t_h2d, ...).
                g.add_edge(prev_h2d[w], io)
            h2d = g.add_task(f"h2d_w{w}", TaskKind.COMM, costs.t_h2d,
                             pcie_channel(w), iteration=it, worker=w)
            g.add_edge(io, h2d)
            # Early H2D (Caffe-MPI's GPU-side buffer) starts right after its
            # fetch; otherwise it must wait for the previous model update
            # (no spare device buffer to write into).
            if prev_update is not None and not policy.h2d_early:
                g.add_edge(prev_update, h2d)
            if prev_h2d:
                g.add_edge(prev_h2d[w], h2d)
            h2d_tasks.append(h2d)

        # --- forward, layer 1..L ---------------------------------------
        # A worker's chain breaks after each all-to-all layer: the
        # layer's dispatch and combine join the workers there.
        a2a = set(self._a2a)
        scale = self._worker_scale
        fwd: list[list[int]] = [[] for _ in range(L)]
        for w in range(self.n_workers):
            ws = 1.0 if scale is None else scale[w]
            prev = h2d_tasks[w]
            for l in range(L):
                t = g.add_task(f"fwd_l{l + 1}_w{w}", TaskKind.COMPUTE,
                               costs.t_f[l] * ws, self._gpu_of(w),
                               iteration=it,
                               layer=l + 1, worker=w, priority=float(l))
                if prev is not None:
                    g.add_edge(prev, t)
                if l == 0 and prev_update is not None:
                    g.add_edge(prev_update, t)
                fwd[l].append(t)
                prev = None if l in a2a else t
        after_fwd = {l: self._all_to_all("fwd", l, fwd[l], it)
                     for l in self._a2a}
        for l, last in after_fwd.items():
            if l + 1 < L:
                for t in fwd[l + 1]:
                    g.add_edge(last, t)

        # --- backward, layer L..1 --------------------------------------
        # An all-to-all layer's backward starts with the gradients of
        # its combine and dispatch, which wait for every worker.
        bwd: dict[int, list[int]] = {}
        for w in range(self.n_workers):
            ws = 1.0 if scale is None else scale[w]
            prev = fwd[L - 1][w]
            for l in range(L - 1, -1, -1):
                t = g.add_task(f"bwd_l{l + 1}_w{w}", TaskKind.COMPUTE,
                               costs.t_b[l] * ws, self._gpu_of(w),
                               iteration=it,
                               layer=l + 1, worker=w,
                               priority=float(2 * L - l))
                if l not in a2a:
                    g.add_edge(prev, t)
                bwd.setdefault(l, []).append(t)
                prev = t
        for l in self._a2a:
            preds = bwd[l + 1] if l + 1 < L else [after_fwd[l]]
            last = self._all_to_all("bwd", l, preds, it)
            for t in bwd[l]:
                g.add_edge(last, t)
        last_bwd = [bwd[0][w] for w in range(self.n_workers)]  # layer 1 last
        # Partial sync: only the K participants' gradients gate the
        # aggregation and the update.  Non-participants keep training
        # (their tasks still occupy their own channels) but nothing
        # downstream waits for them.
        sync = self._sync_workers
        sync_last_bwd = last_bwd if sync is None \
            else [last_bwd[w] for w in sync]

        # --- gradient aggregation (comm tasks T32-T34) -----------------
        comm_tasks: list[int] = []
        prev_comm: int | None = None
        for j, (bname, members, dur) in enumerate(self._buckets):
            # ByteScheduler semantics (policies.py): priority is the
            # bucket's earliest layer — layer-1/earlier-needed
            # tensors overtake on a priority-scheduled net channel
            # (lower value = scheduled first).  ``members`` is in
            # backward order, so the earliest layer is members[-1].
            # Under expert parallelism the bucket's expert all-reduce
            # follows its dense one as a second task.
            parts = [(bname, dur, costs.grad_bytes)]
            if self._expert_durs is not None:
                parts.append((bname + "_experts", self._expert_durs[j],
                              costs.expert_bytes))
            for name, d, nbytes in parts:
                c = g.add_task(name, TaskKind.COMM, d, NET_CHANNEL,
                               iteration=it, layer=members[0] + 1,
                               priority=float(members[-1]),
                               nbytes=sum(nbytes[m] for m in members)
                               if nbytes is not None else 0.0)
                if policy.overlap_comm:
                    # WFBP: ready as soon as every participating worker
                    # finished the backward of every member layer.
                    for m in members:
                        g.add_edges(bwd[m] if sync is None
                                    else [bwd[m][w] for w in sync], c)
                else:
                    # CNTK: aggregation only after the entire backward.
                    g.add_edges(sync_last_bwd, c)
                if prev_comm is not None and policy.serialize_comm:
                    g.add_edge(prev_comm, c)
                prev_comm = c
                comm_tasks.append(c)

        # --- checkpoint restores (crash/recover events) ----------------
        # A crashed worker re-reads the checkpoint before the update may
        # broadcast.  Restores gate on the same predecessors the update
        # would (the sync point is where the crash is detected) and
        # chain on the shared checkpoint store, so an iteration with
        # ``c`` crashes finishes exactly ``c * restart_s`` later.
        restores: list[int] = []
        for w in self._crashed:
            r = g.add_task(f"restore_w{w}", TaskKind.COMM,
                           self._restart_s, CKPT_CHANNEL, iteration=it,
                           worker=w, priority=float(3 * L))
            g.add_edges(sync_last_bwd, r)
            g.add_edges(comm_tasks, r)
            if restores:
                g.add_edge(restores[-1], r)
            restores.append(r)

        # --- model update (T35) ----------------------------------------
        # The update runs on a *participant's* GPU stream: under K-of-N
        # a non-participant straggler keeps its own channel busy past
        # the sync point, and parking the update there would serialize
        # the whole pipeline behind a worker nobody waits for.
        upd = g.add_task("update", TaskKind.COMPUTE, costs.t_u,
                         self._gpu_of(0 if sync is None else sync[0]),
                         iteration=it, priority=float(3 * L + 1))
        g.add_edges(sync_last_bwd, upd)
        g.add_edges(comm_tasks, upd)
        g.add_edges(restores, upd)
        self._prev_update = upd
        self._prev_h2d = h2d_tasks
        self.n_iterations += 1
        return upd

    def _all_to_all(self, phase: str, layer: int, preds: list[int],
                    it: int) -> int:
        """The two all-to-alls of ``layer`` in ``phase`` (``"fwd"``:
        dispatch then combine; ``"bwd"``: their gradients in reverse)
        as a chain on :data:`EP_CHANNEL` after every task of ``preds``;
        returns the second, which the workers' next compute waits for."""
        g, L = self.dag, self.costs.num_layers
        prio = float(layer) if phase == "fwd" else float(2 * L - layer)
        names = ("dispatch", "combine") if phase == "fwd" \
            else ("combine", "dispatch")
        prev = None
        for name in names:
            t = g.add_task(f"a2a_{phase}_{name}_l{layer + 1}", TaskKind.COMM,
                           self.costs.t_a2a[layer], EP_CHANNEL,
                           iteration=it, layer=layer + 1, priority=prio)
            g.add_edges(preds if prev is None else [prev], t)
            prev = t
        return prev


def build_ssgd_dag(
    costs: IterationCosts,
    n_workers: int,
    policy: Policy,
    n_iterations: int = 1,
    comm_scale: Callable[[float, float], float] | None = None,
    expert_comm_scale: Callable[[float, float], float] | None = None,
    shared_compute: bool = False,
    worker_scale: Sequence[float] | None = None,
    sync_k: int | None = None,
    crashed: Sequence[int] = (),
    restart_s: float = 0.0,
) -> DAG:
    """Build the S-SGD DAG of Fig. 1 for ``n_iterations`` iterations.

    Single-GPU training (``n_workers == 1``) degenerates to Eq. (1):
    the comm tasks get zero duration and the graph is a chain.

    ``comm_scale(total_bytes, naive_total_time)`` maps a fused bucket to
    its collective duration (used by the bucketing policy to model the
    latency amortization the paper calls for in §VII);
    ``expert_comm_scale`` does the same for a bucket's expert bytes
    over the expert group, under expert parallelism.
    ``worker_scale`` gives per-worker compute-time multipliers
    (heterogeneous GPUs / straggler jitter draws) — the per-worker DAG
    is the agreement oracle for the heterogeneous batched engine.
    ``sync_k`` enables K-of-N partial synchronization (``None``/``0`` =
    full sync); ``crashed`` workers pay a serialized ``restart_s``
    checkpoint restore before each iteration's update.
    """
    b = SSGDDagBuilder(costs, n_workers, policy, comm_scale=comm_scale,
                       expert_comm_scale=expert_comm_scale,
                       shared_compute=shared_compute,
                       worker_scale=worker_scale, sync_k=sync_k,
                       crashed=crashed, restart_s=restart_s)
    for _ in range(n_iterations):
        b.add_iteration()
    return b.dag
