"""Shared `hypothesis` strategies for the batched-engine test suite.

The random generators that used to live inline in ``test_batched.py``
(the WFBP-residual rng loop) and ``test_bucketsim.py`` (``_rand_costs``)
now live here as composite strategies so every property test draws from
one vocabulary: random per-layer cost vectors, random gradient-payload
rows, and random batched-eligible scenario grids (the NumPy ≡ JAX
differential surface of ``test_batched_jax.py``).

Built on the ``hypothesis`` package (a test dependency).
"""
from __future__ import annotations

import numpy as np
from hypothesis import strategies as st

from repro.core.dag import IterationCosts

#: Bucket-size knobs the DAG builder and the timeline kernel must agree
#: on: per-layer (None), degenerate-small, paper defaults, giant-fused.
BUCKET_BYTES_CHOICES = (None, 1.0, 1e6, 25e6, 1e9)

# Axis vocabularies for random scenario grids — every workload provider
# (cnn:/trace:/llm:) and every built-in batched-eligible policy family.
GRID_WORKLOADS = ("alexnet", "googlenet", "resnet50",
                  "trace:alexnet-k80", "llm:gemma3-1b")
GRID_CLUSTERS = ("k80-pcie-10gbe", "v100-nvlink-ib", "tpu-v5e-pod")
GRID_WORKERS = (1, 2, 4, 8, 16, 32)
GRID_POLICIES = ("naive", "cntk", "mxnet", "tensorflow", "caffe-mpi",
                 "bucketed-1mb", "bucketed-4mb", "bucketed-25mb",
                 "bucketed-100mb", "priority")
GRID_COLLECTIVES = ("ring", "tree", "hierarchical")
GRID_INTERCONNECTS = (None, "ib-100g", "10gbe@bw2@lat0.25",
                      "nvlink@bw0.5@lat4")
GRID_HET_PROFILES = (None, "het:1x0.5+3x1.0", "het:2x1.0@bw0.5",
                     "het:1x0.7@lat2.0+1x1.3", "het:1x1.0")
#: Straggler specs keep draw counts small — property tests run many
#: examples, and the MC cost is (unique points) x draws.
GRID_STRAGGLERS = (None, "lognormal:0.25x32", "exp:0.5x16",
                   "lognormal:0x8")
#: K-of-N partial-sync thresholds (0/None = full sync; K is clamped to
#: the worker count at evaluation, so over-large values are valid).
GRID_SYNC_KS = (None, 0, 1, 2, 3, 7)
#: Fault specs, small draw counts for the same reason as stragglers;
#: ``fail:0`` and ``@restart0`` are the deterministic degenerates.
GRID_FAULTS = (None, "fail:0.1@restart1.5x16", "fail:0.5@restart0.25x8",
               "fail:0x8", "fail:0.3@restart0x8")


@st.composite
def grad_bytes_row(draw, n_layers: int):
    """Per-layer gradient payloads: ~half the layers carry a gradient
    (the rest are parameterless, payload 0), at least one layer does."""
    row = [draw(st.floats(1e5, 8e7)) if draw(st.booleans()) else 0.0
           for _ in range(n_layers)]
    if not any(row):
        row[0] = 1e6
    return row


@st.composite
def iteration_costs(draw, max_layers: int = 12, with_comm: bool = False):
    """Random :class:`~repro.core.dag.IterationCosts` — the generator
    behind the simulator-agreement and bucket-structure properties
    (formerly ``test_bucketsim._rand_costs``).  ``with_comm`` fills
    ``t_c`` on exactly the ``grad_bytes > 0`` layers, matching the
    ``iteration_costs`` contract the DAG builder relies on."""
    L = draw(st.integers(1, max_layers))
    gb = draw(grad_bytes_row(L))
    t_c = [draw(st.floats(0.01, 5.0)) if b > 0 else 0.0 for b in gb] \
        if with_comm else [0.0] * L
    return IterationCosts(
        t_f=[draw(st.floats(1e-3, 5.0)) for _ in range(L)],
        t_b=[draw(st.floats(1e-3, 5.0)) for _ in range(L)],
        t_c=t_c, t_io=draw(st.floats(0.0, 8.0)),
        t_h2d=draw(st.floats(0.0, 3.0)), t_u=draw(st.floats(0.0, 2.0)),
        grad_bytes=gb)


@st.composite
def wfbp_layer_times(draw, max_layers: int = 13):
    """``(t_b, t_c)`` per-layer rows for the WFBP residual property:
    ~60% of layers communicate, the rest have ``t_c = 0`` (formerly the
    inline rng loop of ``test_batched.TestVectorizedWfbpResidual``)."""
    L = draw(st.integers(1, max_layers))
    t_b = np.array([draw(st.floats(0.0, 5.0)) for _ in range(L)])
    t_c = np.array([draw(st.floats(0.0, 5.0))
                    if draw(st.integers(0, 9)) < 6 else 0.0
                    for _ in range(L)])
    return t_b, t_c


def _axis(draw, choices, max_size):
    """A sorted, de-duplicated random axis tuple (order-stable so grid
    cache keys — and therefore drawn examples — are deterministic)."""
    picked = draw(st.lists(st.sampled_from(choices),
                           min_size=1, max_size=max_size))
    return tuple(sorted(set(picked), key=lambda v: str(v)))


@st.composite
def worker_rates(draw, max_workers: int = 8):
    """A per-worker relative-speed vector (each in ``(0, 2]``, at least
    one worker) — raw material for per-worker oracle properties."""
    n = draw(st.integers(1, max_workers))
    return np.array([draw(st.floats(0.1, 2.0)) for _ in range(n)])


@st.composite
def het_profiles(draw, max_slots: int = 3):
    """A random ``het:`` profile string: 1–3 slots with random counts,
    relative speeds, and optional per-slot bandwidth/latency skew."""
    slots = []
    for _ in range(draw(st.integers(1, max_slots))):
        s = f"{draw(st.integers(1, 4))}x{draw(st.floats(0.25, 2.0)):g}"
        if draw(st.booleans()):
            s += f"@bw{draw(st.floats(0.25, 2.0)):g}"
        if draw(st.booleans()):
            s += f"@lat{draw(st.floats(0.5, 4.0)):g}"
        slots.append(s)
    return "het:" + "+".join(slots)


@st.composite
def straggler_specs(draw, max_draws: int = 32):
    """A random parsed-valid straggler spec string; scale 0 (the
    deterministic degenerate) is drawn deliberately often."""
    dist = draw(st.sampled_from(("lognormal", "exp")))
    scale = draw(st.sampled_from((0.0, 0.1, 0.25, 0.5)))
    return f"{dist}:{scale:g}x{draw(st.integers(4, max_draws))}"


@st.composite
def sync_ks(draw, max_k: int = 8):
    """A random K-of-N threshold: ``None``/``0`` (full sync) or a
    positive K — deliberately allowed to exceed the worker count, since
    the engine clamps (``K >= n`` must be bit-identical to full
    sync)."""
    if draw(st.booleans()):
        return draw(st.sampled_from((None, 0)))
    return draw(st.integers(1, max_k))


@st.composite
def fault_specs(draw, max_draws: int = 32):
    """A random parsed-valid ``fail:`` spec string; ``p = 0`` and
    ``restart = 0`` (the deterministic degenerates) are drawn
    deliberately often."""
    p = draw(st.sampled_from((0.0, 0.05, 0.2, 0.5)))
    restart = draw(st.sampled_from((0.0, 0.5, 2.5)))
    return (f"fail:{p:g}@restart{restart:g}"
            f"x{draw(st.integers(4, max_draws))}")


@st.composite
def scenario_grids(draw, max_per_axis: int = 2, with_het: bool = False,
                   with_failures: bool = False):
    """Random batched-eligible :class:`~repro.core.scenarios.ScenarioGrid`
    spanning every provider, policy family, collective and interconnect
    preset — the NumPy ≡ JAX differential property's input space.
    ``with_het=True`` adds the heterogeneity axes (het profiles and
    small-draw straggler specs); ``with_failures=True`` the failure
    axes (K-of-N sync thresholds and fault specs)."""
    from repro.core.scenarios import ScenarioGrid

    extra_axes = {}
    if with_het:
        extra_axes = {
            "het_profiles": _axis(draw, GRID_HET_PROFILES, max_per_axis),
            "stragglers": _axis(draw, GRID_STRAGGLERS, max_per_axis)}
    if with_failures:
        extra_axes["sync_ks"] = _axis(draw, GRID_SYNC_KS, max_per_axis)
        extra_axes["faults"] = _axis(draw, GRID_FAULTS, max_per_axis)
    return ScenarioGrid(
        workloads=_axis(draw, GRID_WORKLOADS, max_per_axis),
        clusters=_axis(draw, GRID_CLUSTERS, max_per_axis),
        worker_counts=_axis(draw, GRID_WORKERS, max_per_axis),
        policies=_axis(draw, GRID_POLICIES, max_per_axis),
        collectives=_axis(draw, GRID_COLLECTIVES, max_per_axis),
        interconnects=_axis(draw, GRID_INTERCONNECTS, max_per_axis),
        **extra_axes)
