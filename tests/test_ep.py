"""Expert parallelism (EP) in the what-if engine: both batched backends
against the event-driven DAG oracle, the ``ep = 1`` identity, the
validation of a layout, the DAG's all-to-all tasks and the all-to-all
cost model."""
import dataclasses

import numpy as np
import pytest

from repro.configs import get_config
from repro.core import workloads as W
from repro.core.dag import EP_CHANNEL, SSGDDagBuilder
from repro.core.hardware import (CLUSTERS, COLLECTIVE_ALGORITHMS,
                                 alltoall_coeffs)
from repro.core.policies import get_policy
from repro.core.scenarios import (FRONTIER_POLICIES, Scenario, ScenarioGrid,
                                  frontier_grid, resolve_cluster)
from repro.core.sweep import sweep

#: DeepSeek-V2-Lite at a CPU size: a dense layer and three MoE layers
#: of 8 routed experts (top-6, 2 shared), MLA widths cut with d_model.
REDUCED = get_config("deepseek-v2-lite").reduced(
    num_layers=4, d_model=512, num_heads=4, d_ff=1024, num_experts=8)
NUMERIC = ("iteration_time_s", "samples_per_sec", "speedup", "t_comm_s",
           "t_comp_s", "t_mean_s", "t_p95_s", "t_p99_s")


class _Reduced:
    scheme = "dsv2r"

    def names(self):
        return ("lite",)

    def build(self, spec):
        if spec != "lite":
            raise ValueError(spec)
        return W.llm_table(REDUCED, "dsv2r:lite")


@pytest.fixture(scope="module")
def lite():
    W.register_provider(_Reduced())
    try:
        yield "dsv2r:lite"
    finally:
        del W.WORKLOAD_PROVIDERS["dsv2r"]
        W.clear_workload_cache()


def _grid(workload, n, ep, het=None) -> ScenarioGrid:
    return ScenarioGrid(workloads=(workload,), clusters=("v100-nvlink-ib",),
                        worker_counts=(n,), ep_sizes=(ep,),
                        policies=FRONTIER_POLICIES,
                        collectives=COLLECTIVE_ALGORITHMS,
                        het_profiles=(het,))


def _assert_close(got, want, rel, columns=NUMERIC):
    for c in columns:
        np.testing.assert_allclose(got.columns[c], want.columns[c], rtol=rel,
                                   atol=0, err_msg=c)
    for c in ("policy", "collective", "ep_size", "n_workers", "het"):
        assert (got.columns[c] == want.columns[c]).all(), c


# ----------------------------------------------------------------------
# The batched backends against the DAG oracle.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("n,ep,het", [
    (4, 1, None), (4, 2, None), (4, 4, None),
    (8, 1, None), (8, 2, None), (8, 4, None),
    (8, 4, "het:1x0.5+3x1.0"), (4, 2, "het:1x1.0@bw0.5+1x1.0")])
def test_batched_backends_agree_with_the_dag_oracle(lite, n, ep, het):
    """Every policy and collective on the v100 preset (4 devices a node:
    ``ep = 4, n = 8`` spans nodes in the dense and expert groups)."""
    grid = _grid(lite, n, ep, het)
    oracle = sweep(grid, force_simulator=True)
    assert oracle.n_simulated == len(grid) == 30
    # the simulator reports a heterogeneous row's compute unscaled, the
    # batched engines at the slowest worker's rate (as without EP)
    cols = NUMERIC if het is None else tuple(
        c for c in NUMERIC if c != "t_comp_s")
    for got in (sweep(grid), sweep(grid, backend="jax"),
                sweep(grid, batched=False)):
        _assert_close(got, oracle, 1e-6, cols)
    assert (oracle.columns["ep_size"] == ep).all()


def test_all_to_alls_and_expert_group_cost_what_ep_moves(lite):
    """At ep > 1 the all-to-alls join the compute and the routed
    gradients shrink by ep; ep = 1 has neither."""
    one = sweep(_grid(lite, 8, 1)).columns
    four = sweep(_grid(lite, 8, 4)).columns
    assert (four["t_comp_s"] > one["t_comp_s"]).all()
    assert (four["t_comm_s"] < one["t_comm_s"]).all()


def test_list_front_ends_match_the_grid(lite):
    """The scenario-list paths (the sweep service's coalescer) carry
    ``ep_size`` like the grid path, on both backends."""
    grid = dataclasses.replace(_grid(lite, 8, 1), ep_sizes=(1, 2, 4))
    want = sweep(grid)
    for backend in ("numpy", "jax"):
        got = sweep(list(grid.expand()), backend=backend)
        _assert_close(got, want, 1e-12)


# ----------------------------------------------------------------------
# ep = 1 is the engine without expert parallelism, bit for bit.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["numpy", "jax"])
def test_ep1_rows_of_an_ep_grid_are_bit_identical(backend):
    base = ScenarioGrid(workloads=("llm:qwen2-moe-a2.7b",),
                        clusters=("v100-nvlink-ib", "tpu-v5e-pod"),
                        worker_counts=(4, 8, 16),
                        policies=FRONTIER_POLICIES,
                        collectives=COLLECTIVE_ALGORITHMS,
                        interconnects=("ib-100g@bw2@lat0.25", "10gbe"))
    plain = sweep(base, backend=backend).columns
    mixed = sweep(dataclasses.replace(base, ep_sizes=(1, 2, 4)),
                  backend=backend).columns
    one = mixed["ep_size"] == 1
    for c in NUMERIC:
        assert np.array_equal(mixed[c][one], plain[c]), c
    assert (plain["ep_size"] == 1).all()


def test_grids_without_ep_build_no_ep_structure():
    """A grid whose every ``ep_size`` is 1 (the frontier) builds no EP
    tables and hands the jax kernel no EP codes, so the kernel traces as
    it did before the axis."""
    from repro.core.batched import grid_evaluator
    from repro.core.batched_jax import JaxGridEvaluator

    grid = frontier_grid()
    assert grid.ep_sizes == (1,)
    assert grid_evaluator(grid).points.epx is None
    jev = JaxGridEvaluator(grid)
    assert "we" not in jev._kcodes and "ep" not in jev._kcodes
    assert not any(k.startswith("ep_") for k in jev._tables)
    assert jev._points == (len(jev._kcodes["n"]), 0)


# ----------------------------------------------------------------------
# Validation.
# ----------------------------------------------------------------------
@pytest.mark.parametrize("workload,cluster,n,ep,sync_k,match", [
    ("llm:deepseek-v2-lite", "v100-nvlink-ib", 8, 3, None, "divide n_workers"),
    ("llm:deepseek-v2-lite", "v100-nvlink-ib", 12, 3, None, "routed experts"),
    ("resnet50", "v100-nvlink-ib", 8, 2, None, "needs routed experts"),
    ("llm:qwen2-moe-a2.7b", "v100-nvlink-ib", 12, 3, None, "a node"),
    ("llm:deepseek-v2-lite", "v100-nvlink-ib", 8, 2, 4, "full synchron"),
    ("llm:deepseek-v2-lite", "v100-nvlink-ib", 8, 0, None, "positive int"),
])
def test_a_bad_ep_is_refused(workload, cluster, n, ep, sync_k, match):
    s = Scenario(workload, cluster, n, "tensorflow", ep_size=ep,
                 sync_k=sync_k)
    with pytest.raises(ValueError, match=match):
        s.validate()
    grid = ScenarioGrid(workloads=(workload,), clusters=(cluster,),
                        worker_counts=(n,), ep_sizes=(1, ep),
                        sync_ks=(sync_k,))
    with pytest.raises(ValueError, match=match):
        grid.validate_axes()


def test_good_layouts_validate_and_label():
    s = Scenario("llm:deepseek-v2-lite", "tpu-v5e-pod", 512, "caffe-mpi",
                 ep_size=64)
    s.validate()
    assert s.label().endswith("/ep64")
    assert "/ep" not in dataclasses.replace(s, ep_size=1).label()


# ----------------------------------------------------------------------
# The DAG and the cost model.
# ----------------------------------------------------------------------
def test_dag_puts_four_all_to_alls_per_moe_layer_on_their_channel(lite):
    s = Scenario(lite, "v100-nvlink-ib", 8, "tensorflow", ep_size=4)
    tab = W.resolve_workload(lite)
    costs = tab.iteration_costs(resolve_cluster(s), 1, 8, "ring", ep=4)
    b = SSGDDagBuilder(costs, 8, get_policy("tensorflow"))
    b.add_iteration()
    a2a = [t for t in b.dag.tasks.values() if t.channel == EP_CHANNEL]
    moe = int((tab.a2a_bytes_per_sample > 0).sum())
    assert moe == REDUCED.num_layers - REDUCED.first_k_dense
    assert len(a2a) == 4 * moe
    assert all(t.duration > 0 for t in a2a)
    experts = [t for t in b.dag.tasks.values()
               if t.name.endswith("_experts")]
    assert len(experts) == len(tab.grad_bytes)      # one per layer
    with pytest.raises(ValueError, match="partial sync"):
        SSGDDagBuilder(costs, 8, get_policy("tensorflow"), sync_k=6)


@pytest.mark.parametrize("n", [1, 2, 4, 8, 64])
def test_alltoall_coeffs_reproduce_alltoall_time(n):
    cluster = CLUSTERS["v100-nvlink-ib"]
    link = cluster.intra if n <= cluster.gpus_per_node else cluster.inter
    per_byte, per_message = alltoall_coeffs(n, link.effective_bandwidth,
                                            link.latency)
    for nbytes in (1e3, 2.5e7):
        assert per_byte * nbytes + per_message \
            == cluster.alltoall_time(nbytes, n)
    if n > 1:
        assert per_message == (n - 1) * link.latency
    else:
        assert per_byte == per_message == 0.0


def test_expert_group_holds_the_strided_ranks_of_a_node():
    cluster = CLUSTERS["v100-nvlink-ib"]           # 4 devices a node
    assert cluster.expert_group(1) is cluster
    assert cluster.expert_group(2).gpus_per_node == 2
    assert cluster.expert_group(8).gpus_per_node == 1
