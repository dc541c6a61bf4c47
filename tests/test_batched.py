"""Scenario-axis batched fast path (ISSUE 3): agreement with the
per-scenario oracle on every built-in grid, array-valued collective
models, the frontier grid + scaled-preset grammar, streaming emission,
and the priority/steady-state/filter bugfix pass."""
import dataclasses
import json

import numpy as np
import pytest
from hypothesis import given, settings
from strategies import wfbp_layer_times

from repro.core import analytical as A
from repro.core import hardware as HW
from repro.core.batched import eval_scenarios
from repro.core.dag import IterationCosts, build_ssgd_dag
from repro.core.policies import CAFFE_MPI, PRIORITY, get_policy
from repro.core.scenarios import (Scenario, ScenarioGrid, default_grid,
                                  frontier_grid, mixed_grid,
                                  normalize_interconnect)
from repro.core.simulator import NET_CHANNEL, simulate_policy, simulate_steady
from repro.core.sweep import (_fast_eval, iter_rows, stream_csv, stream_json,
                              sweep)

NUMERIC = ("iteration_time_s", "samples_per_sec", "speedup",
           "t_comm_s", "t_comp_s")
LABELS = ("workload", "cluster", "n_workers", "policy", "collective",
          "interconnect", "batch_per_gpu", "method")


def assert_rows_agree(batched_rows, oracle_rows, rel=1e-9):
    assert len(batched_rows) == len(oracle_rows)
    for a, b in zip(batched_rows, oracle_rows):
        assert {k: a[k] for k in LABELS} == {k: b[k] for k in LABELS}
        for k in NUMERIC:
            assert a[k] == pytest.approx(b[k], rel=rel), (a, k)


class TestBatchedAgreement:
    """ISSUE-3 acceptance: the batched kernel agrees with the
    per-scenario reference `_fast_eval` to <= 1e-9 relative on the
    default, mixed and frontier grids."""

    @pytest.mark.parametrize("make_grid", [default_grid, mixed_grid],
                             ids=["default", "mixed"])
    def test_full_grid_agreement(self, make_grid):
        grid = make_grid()
        batched = sweep(grid)
        oracle = [_fast_eval(s) for s in grid.expand()]
        assert_rows_agree(batched.rows, oracle)

    def test_frontier_grid_agreement_sampled_plus_sweep(self):
        grid = frontier_grid()
        batched = sweep(grid)
        assert batched.n_simulated == 0
        assert batched.n_timeline > 0          # bucket-size + priority axis
        scenarios = grid.expand()
        assert len(batched) == len(scenarios) >= 20_000
        # oracle every 37th scenario (coprime stride covers every axis
        # value) — the full per-scenario pass is benchmarked, not
        # tested.  Closed-form rows check against _fast_eval (<=1e-9);
        # timeline rows against the event-driven simulator (<=1e-6),
        # sampled sparser because each oracle call list-schedules a DAG.
        idx = [i for i in range(0, len(scenarios), 37)
               if batched.rows[i]["method"] == "analytical"]
        assert idx
        assert_rows_agree([batched.rows[i] for i in idx],
                          [_fast_eval(scenarios[i]) for i in idx])
        from repro.core.sweep import _sim_eval
        tl_idx = [i for i in range(0, len(scenarios), 331)
                  if batched.rows[i]["method"] == "timeline"]
        assert tl_idx
        for i in tl_idx:
            assert batched.rows[i]["iteration_time_s"] == pytest.approx(
                _sim_eval(scenarios[i])["iteration_time_s"], rel=1e-6), \
                scenarios[i].label()

    def test_batched_false_uses_reference_path(self):
        grid = ScenarioGrid(workloads=("alexnet",), worker_counts=(4,),
                            policies=("caffe-mpi",))
        assert_rows_agree(sweep(grid, batched=False).rows,
                          [_fast_eval(s) for s in grid.expand()], rel=0)

    def test_row_order_matches_expand(self):
        grid = ScenarioGrid(workloads=("alexnet", "googlenet"),
                            worker_counts=(2, 8), policies=("naive", "mxnet"),
                            collectives=("ring", "tree"))
        rows = sweep(grid).rows
        for row, s in zip(rows, grid.expand()):
            assert (row["workload"], row["cluster"], row["n_workers"],
                    row["policy"], row["collective"]) == \
                (s.workload, s.cluster, s.n_workers, s.policy, s.collective)

    def test_timeline_rows_interleaved_in_order(self):
        grid = ScenarioGrid(workloads=("alexnet",),
                            clusters=("v100-nvlink-ib",), worker_counts=(4,),
                            policies=("caffe-mpi", "bucketed-25mb",
                                      "priority"))
        r = sweep(grid)
        assert r.n_analytical == 1 and r.n_timeline == 2 \
            and r.n_simulated == 0
        assert [row["method"] for row in r.rows] == \
            ["analytical", "timeline", "timeline"]
        # the timeline rows agree with the event-driven oracle
        from repro.core.sweep import _sim_eval
        for row, s in zip(r.rows, grid.expand()):
            if row["method"] == "timeline":
                assert row["iteration_time_s"] == pytest.approx(
                    _sim_eval(s)["iteration_time_s"], rel=1e-6)

    def test_simulator_rows_interleaved_in_order(self):
        # policies with neither closed nor timeline form still fall
        # back to the simulator, interleaved in grid order
        from repro.core import policies as P
        from repro.core.policies import Policy
        weird = Policy("_unstudied", overlap_comm=True)   # no io overlap
        P.ALL_POLICIES["_unstudied"] = weird
        try:
            grid = ScenarioGrid(workloads=("alexnet",),
                                clusters=("v100-nvlink-ib",),
                                worker_counts=(4,),
                                policies=("caffe-mpi", "_unstudied"))
            r = sweep(grid)
            assert r.n_analytical == 1 and r.n_timeline == 0 \
                and r.n_simulated == 1
            assert [row["method"] for row in r.rows] == \
                ["analytical", "simulated"]
            from repro.core.sweep import _sim_eval
            for row, s in zip(r.rows, grid.expand()):
                if row["method"] == "simulated":
                    assert row["iteration_time_s"] == pytest.approx(
                        _sim_eval(s)["iteration_time_s"])
        finally:
            del P.ALL_POLICIES["_unstudied"]

    def test_eval_scenarios_list_front_end(self):
        scenarios = ScenarioGrid(workloads=("resnet50",),
                                 worker_counts=(1, 16),
                                 policies=("cntk", "tensorflow")).expand()
        assert_rows_agree(eval_scenarios(scenarios),
                          [_fast_eval(s) for s in scenarios])

    def test_eval_scenarios_accepts_timeline_policies(self):
        from repro.core.sweep import _sim_eval
        scenarios = [Scenario("alexnet", "v100-nvlink-ib", 4,
                              "bucketed-25mb"),
                     Scenario("alexnet", "v100-nvlink-ib", 4, "priority")]
        rows = eval_scenarios(scenarios)
        assert [r["method"] for r in rows] == ["timeline", "timeline"]
        for row, s in zip(rows, scenarios):
            assert row["iteration_time_s"] == pytest.approx(
                _sim_eval(s)["iteration_time_s"], rel=1e-6)

    def test_eval_scenarios_rejects_unbatchable_policies(self):
        from repro.core import policies as P
        from repro.core.policies import Policy
        P.ALL_POLICIES["_unstudied"] = Policy("_unstudied", h2d_early=True)
        try:
            with pytest.raises(ValueError, match="batched"):
                eval_scenarios([Scenario("alexnet", "v100-nvlink-ib", 4,
                                         "_unstudied")])
        finally:
            del P.ALL_POLICIES["_unstudied"]

    def test_empty_grid_and_empty_iterable(self):
        assert len(sweep(ScenarioGrid(workloads=()))) == 0
        assert len(sweep(iter([]))) == 0

    def test_sweep_accepts_plain_scenario_list(self):
        scenarios = [Scenario("alexnet", "k80-pcie-10gbe", 8, "caffe-mpi"),
                     Scenario("alexnet", "k80-pcie-10gbe", 8, "priority")]
        r = sweep(scenarios)
        assert [row["method"] for row in r.rows] == ["analytical",
                                                     "timeline"]
        assert r.n_analytical == 1 and r.n_timeline == 1
        # batched=False pins the per-scenario reference paths instead
        ref = sweep(scenarios, batched=False)
        assert [row["method"] for row in ref.rows] == ["analytical",
                                                       "simulated"]

    def test_batch_override_propagates(self):
        grid = ScenarioGrid(workloads=("resnet50",),
                            clusters=("v100-nvlink-ib",), worker_counts=(4,),
                            policies=("caffe-mpi",), batch_per_gpu=8)
        [row] = sweep(grid).rows
        assert row["batch_per_gpu"] == 8
        assert_rows_agree([row], [_fast_eval(grid.expand()[0])])

    def test_locked_trace_batch_override_rejected(self):
        from repro.traces.format import LayerRecord, Trace
        import repro.traces.bundled as bundled
        from repro.core.workloads import clear_workload_cache

        trace = Trace(network="x", cluster="y", iterations=(
            (LayerRecord(0, "conv1", 10.0, 20.0, 0.0, 4096),),))
        assert trace.batch_per_gpu == 0          # no '# batch:' header
        bundled.BUNDLED_TRACES["_locked_test"] = trace
        try:
            clear_workload_cache()
            grid = ScenarioGrid(workloads=("trace:_locked_test",),
                                clusters=("v100-nvlink-ib",),
                                worker_counts=(2,), policies=("caffe-mpi",),
                                batch_per_gpu=64)
            with pytest.raises(ValueError, match="no recorded batch"):
                sweep(grid)
        finally:
            del bundled.BUNDLED_TRACES["_locked_test"]
            clear_workload_cache()


class TestMeasuredComputeWithoutMeasuredIO:
    """Regression: a trace without a Caffe 'data' layer has measured
    t_f/t_b but no measured t_io — the batched kernel must not gate
    the measured compute terms on measured-I/O presence."""

    def test_agrees_with_oracle(self):
        from repro.traces.format import LayerRecord, Trace
        import repro.traces.bundled as bundled
        from repro.core.workloads import clear_workload_cache

        trace = Trace(network="x", cluster="y", iterations=(
            (LayerRecord(0, "conv1", 30_000.0, 60_000.0, 0.0, 4e6),
             LayerRecord(1, "fc", 10_000.0, 20_000.0, 0.0, 16e6)),),
            batch_per_gpu=16)
        bundled.BUNDLED_TRACES["_no_data_test"] = trace
        try:
            clear_workload_cache()
            scenarios = ScenarioGrid(
                workloads=("trace:_no_data_test",),
                clusters=("v100-nvlink-ib",), worker_counts=(1, 8),
                policies=("caffe-mpi", "naive")).expand()
            rows = eval_scenarios(scenarios)
            assert_rows_agree(rows, [_fast_eval(s) for s in scenarios])
            assert all(r["t_comp_s"] > 0 for r in rows)
        finally:
            del bundled.BUNDLED_TRACES["_no_data_test"]
            clear_workload_cache()


class TestVectorizedWfbpResidual:
    @settings(max_examples=200, deadline=None)
    @given(wfbp_layer_times())
    def test_prefix_max_matches_scalar_loop(self, times):
        t_b, t_c = times
        got = A.non_overlapped_comm_batch(t_b[None, :], t_c[None, :])[0]
        want = A.non_overlapped_comm(list(t_b), list(t_c))
        # the vectorized form differences suffix sums of the layer
        # times, so its rounding error scales with their total, not
        # with the (possibly tiny) residual itself
        scale = float(np.sum(t_b) + np.sum(t_c))
        assert got == pytest.approx(
            want, rel=1e-12, abs=8 * np.finfo(np.float64).eps * scale)

    def test_zero_padding_is_neutral(self):
        t_b = np.array([[1.0, 2.0, 3.0]])
        t_c = np.array([[0.5, 4.0, 0.0]])
        pad_b = np.pad(t_b, ((0, 0), (0, 5)))
        pad_c = np.pad(t_c, ((0, 0), (0, 5)))
        assert A.non_overlapped_comm_batch(pad_b, pad_c)[0] == \
            pytest.approx(A.non_overlapped_comm_batch(t_b, t_c)[0])

    def test_no_comm_gives_zero(self):
        z = A.non_overlapped_comm_batch(np.ones((3, 4)), np.zeros((3, 4)))
        assert (z == 0.0).all()


class TestArrayValuedCollectives:
    """hardware.py's collective models broadcast per-scenario
    (n, bandwidth, latency) vectors — and agree with the scalar path."""

    def test_ring_tree_match_scalar(self):
        nbytes = np.array([1e4, 1e6, 25e6])
        for n in (1, 2, 5, 16, 64):
            for fn in (HW.ring_allreduce_time, HW.tree_allreduce_time):
                vec = fn(nbytes[None, :], np.array([n])[:, None],
                         10 * HW.GB, 10 * HW.US)
                scal = fn(nbytes, n, 10 * HW.GB, 10 * HW.US)
                np.testing.assert_allclose(vec[0], scal, rtol=0)

    def test_hierarchical_matches_cluster_method(self):
        c = HW.V100_CLUSTER
        nbytes = np.array([4096.0, 1e6, 102e6])
        for n in (1, 2, 4, 6, 16, 32):
            vec = HW.hierarchical_allreduce_time(
                nbytes[None, :], np.array([n])[:, None],
                np.array([c.gpus_per_node])[:, None],
                c.intra.effective_bandwidth, c.intra.latency,
                c.inter.effective_bandwidth, c.inter.latency)
            scal = c.allreduce_time(nbytes, n, "hierarchical")
            np.testing.assert_allclose(vec[0], scal, rtol=0)


class TestHierarchicalDegenerateCases:
    """Satellite: _hierarchical_allreduce_time edge topologies."""

    def test_single_node_equals_flat_intra_ring(self):
        c = HW.V100_CLUSTER
        for n in (2, 3, c.gpus_per_node):
            assert c.allreduce_time(25e6, n, "hierarchical") == \
                pytest.approx(HW.ring_allreduce_time(
                    25e6, n, c.intra.effective_bandwidth, c.intra.latency))

    def test_one_gpu_per_node_equals_flat_inter_ring(self):
        c = dataclasses.replace(HW.V100_CLUSTER, n_nodes=8, gpus_per_node=1)
        for n in (2, 5, 8):
            assert c.allreduce_time(25e6, n, "hierarchical") == \
                pytest.approx(HW.ring_allreduce_time(
                    25e6, n, c.inter.effective_bandwidth, c.inter.latency))

    def test_n_not_divisible_by_gpus_per_node(self):
        c = HW.V100_CLUSTER                    # 4 GPUs/node
        # n=6 -> g=4, nodes=ceil(6/4)=2: intra phase + 2-node inter ring
        t = c.allreduce_time(25e6, 6, "hierarchical")
        intra = 2.0 * ((4 - 1) / 4 * 25e6 / c.intra.effective_bandwidth
                       + 3 * c.intra.latency)
        inter = HW.ring_allreduce_time(25e6 / 4, 2,
                                       c.inter.effective_bandwidth,
                                       c.inter.latency)
        assert t == pytest.approx(intra + inter)

    def test_single_worker_is_free(self):
        assert HW.V100_CLUSTER.allreduce_time(25e6, 1, "hierarchical") == 0.0


class TestScaledPresetGrammar:
    def test_resolve_scales_bandwidth_and_latency(self):
        slot, link = HW.resolve_interconnect_preset("ib-100g@bw2@lat0.25")
        base = HW.INTERCONNECT_PRESETS["ib-100g"][1]
        assert slot == "inter"
        assert link.bandwidth == pytest.approx(2 * base.bandwidth)
        assert link.latency == pytest.approx(0.25 * base.latency)
        assert link.efficiency == base.efficiency

    def test_modifiers_optional_and_order_free(self):
        _, a = HW.resolve_interconnect_preset("10gbe@lat4")
        _, b = HW.resolve_interconnect_preset("10gbe@lat4@bw1")
        assert a.latency == b.latency and a.bandwidth == b.bandwidth

    @pytest.mark.parametrize("bad", [
        "nope@bw2", "ib-100g@speed2", "ib-100g@bw0", "ib-100g@bw-1",
        "ib-100g@bwx"])
    def test_malformed_rejected(self, bad):
        with pytest.raises((KeyError, ValueError)):
            HW.resolve_interconnect_preset(bad)

    def test_scenario_validate_accepts_scaled_preset(self):
        Scenario("alexnet", "k80-pcie-10gbe", 16, "caffe-mpi",
                 interconnect="ib-100g@bw4@lat0.25").validate()
        with pytest.raises(ValueError, match="interconnect"):
            Scenario("alexnet", "k80-pcie-10gbe", 16, "caffe-mpi",
                     interconnect="ib-100g@frob2").validate()

    def test_more_bandwidth_never_slower(self):
        kw = dict(workloads=("resnet50",), clusters=("k80-pcie-10gbe",),
                  worker_counts=(8, 16), policies=("caffe-mpi", "cntk"),
                  collectives=HW.COLLECTIVE_ALGORITHMS)
        slow = sweep(ScenarioGrid(interconnects=("10gbe",), **kw))
        fast = sweep(ScenarioGrid(interconnects=("10gbe@bw4@lat0.25",), **kw))
        for a, b in zip(slow.rows, fast.rows):
            assert b["iteration_time_s"] <= a["iteration_time_s"] + 1e-12


class TestBuiltinGrids:
    @pytest.mark.parametrize("make_grid", [default_grid, mixed_grid,
                                           frontier_grid],
                             ids=["default", "mixed", "frontier"])
    def test_len_equals_expand(self, make_grid):
        g = make_grid()
        assert len(g) == len(g.expand())

    def test_frontier_size_and_axes(self):
        g = frontier_grid()
        assert len(g) >= 20_000
        # every interconnect is a scaled preset that resolves
        for ic in g.interconnects:
            HW.resolve_interconnect_preset(ic)


class TestStreaming:
    def test_stream_csv_matches_buffered(self, tmp_path):
        import csv

        grid = ScenarioGrid(workloads=("alexnet",), worker_counts=(2, 4),
                            policies=("naive", "caffe-mpi", "bucketed-25mb"))
        buffered = sweep(grid)
        p_buf, p_stream = tmp_path / "buf.csv", tmp_path / "stream.csv"
        buffered.to_csv(p_buf)
        summary = stream_csv(grid, p_stream, chunk=2)
        assert summary["n_scenarios"] == len(buffered)
        assert summary["n_analytical"] == buffered.n_analytical
        assert summary["n_simulated"] == buffered.n_simulated
        with open(p_buf) as f:
            want = list(csv.DictReader(f))
        with open(p_stream) as f:
            got = list(csv.DictReader(f))
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a["workload"] == b["workload"]
            assert float(a["iteration_time_s"]) == pytest.approx(
                float(b["iteration_time_s"]))

    def test_stream_json_document_shape(self, tmp_path):
        grid = ScenarioGrid(workloads=("googlenet",),
                            clusters=("k80-pcie-10gbe",), worker_counts=(2,),
                            policies=("mxnet",))
        path = tmp_path / "sweep.json"
        stream_json(grid, path)
        doc = json.loads(path.read_text())
        buffered = json.loads(sweep(grid).to_json())
        assert set(doc) == set(buffered)
        assert doc["columns"] == buffered["columns"]
        assert doc["n_scenarios"] == len(doc["rows"]) == 1
        assert doc["rows"][0]["iteration_time_s"] == pytest.approx(
            buffered["rows"][0]["iteration_time_s"])

    def test_stream_both_formats_single_pass(self, tmp_path):
        from repro.core.sweep import stream

        grid = ScenarioGrid(workloads=("alexnet",),
                            clusters=("k80-pcie-10gbe",),
                            worker_counts=(2, 4), policies=("caffe-mpi",))
        p_csv, p_json = tmp_path / "s.csv", tmp_path / "s.json"
        summary = stream(grid, csv_path=p_csv, json_path=p_json)
        assert summary["n_scenarios"] == 2
        doc = json.loads(p_json.read_text())
        assert doc["n_scenarios"] == len(doc["rows"]) == 2
        assert p_csv.read_text().count("\n") == 3       # header + 2 rows
        with pytest.raises(ValueError, match="csv_path"):
            stream(grid)

    def test_iter_rows_is_lazy_and_ordered(self):
        grid = ScenarioGrid(workloads=("alexnet",),
                            clusters=("k80-pcie-10gbe",),
                            worker_counts=(2, 4, 8), policies=("naive",))
        it = iter_rows(grid, chunk=1)
        first = next(it)
        assert first["n_workers"] == 2
        assert [r["n_workers"] for r in it] == [4, 8]


class TestPriorityCommBugfix:
    """Satellite: comm priorities were inverted (layer-L drained
    first); ByteScheduler semantics say earlier-needed layers overtake.
    """

    def _comm_bound_costs(self, L=4):
        # tiny backward, long comms: everything is queued on the net
        # channel nearly at once, so scheduling order is priority-driven
        return IterationCosts(
            t_f=[1e-4] * L, t_b=[1e-4] * L,
            t_c=[0.3, 0.2, 0.2, 0.2], t_io=1e-4, t_h2d=1e-4, t_u=1e-4,
            grad_bytes=[1e6] * L)

    def test_priority_assignment_increases_with_layer(self):
        g = build_ssgd_dag(self._comm_bound_costs(), 2, PRIORITY,
                           n_iterations=1)
        comms = sorted((t for t in g.tasks.values()
                        if t.channel == NET_CHANNEL),
                       key=lambda t: t.layer)
        prios = [t.priority for t in comms]
        assert prios == sorted(prios), \
            "earlier layers must carry smaller (= stronger) priority"

    def test_priority_drains_layer1_before_late_layers(self):
        costs = self._comm_bound_costs()
        res = simulate_policy(costs, 2, PRIORITY, n_iterations=1)
        order = [s.task.layer for s in res.tasks_on(NET_CHANNEL)]
        # layer 4's comm is ready first (backward runs L..1) but once
        # the channel frees, the earliest-needed queued layer wins:
        assert order[0] == 4
        assert order[1:] == sorted(order[1:]), order

    def test_priority_no_worse_than_fifo_on_comm_bound_workload(self):
        costs = self._comm_bound_costs()
        t_prio = simulate_steady(costs, 4, PRIORITY)
        t_fifo = simulate_steady(costs, 4, CAFFE_MPI)
        assert t_prio <= t_fifo + 1e-12

    def test_priority_no_worse_than_fifo_on_paper_workload(self):
        from repro.core.workloads import resolve_workload
        from repro.core.scenarios import resolve_cluster

        s = Scenario("resnet50", "v100-nvlink-ib", 16, "priority")
        tab = resolve_workload(s.workload)
        costs = tab.iteration_costs(resolve_cluster(s), tab.batch_default,
                                    s.n_workers)
        t_prio = simulate_steady(costs, s.n_workers, PRIORITY)
        t_fifo = simulate_steady(costs, s.n_workers, CAFFE_MPI)
        assert t_prio <= t_fifo + 1e-12


class TestSteadyStateEmptySchedule:
    """Satellite: zero update tasks raised IndexError deep in list
    indexing; now a clear ValueError."""

    def test_zero_iterations_raises_value_error(self):
        costs = IterationCosts(t_f=[1.0], t_b=[1.0], t_c=[0.5])
        res = simulate_policy(costs, 2, CAFFE_MPI, n_iterations=0)
        assert res.iteration_times() == []
        with pytest.raises(ValueError, match="no 'update' task"):
            res.steady_iteration_time()

    def test_custom_dag_without_update_raises_value_error(self):
        from repro.core.dag import DAG, TaskKind
        from repro.core.simulator import simulate

        g = DAG()
        g.add_task("lonely", TaskKind.COMPUTE, 1.0, "gpu:0")
        res = simulate(g)
        with pytest.raises(ValueError, match="no 'update' task"):
            res.steady_iteration_time()

    def test_one_iteration_still_works(self):
        costs = IterationCosts(t_f=[1.0], t_b=[1.0], t_c=[0.5])
        res = simulate_policy(costs, 2, CAFFE_MPI, n_iterations=1)
        assert res.steady_iteration_time() > 0


class TestInterconnectFilterNormalization:
    """Satellite: filter(interconnect=None) silently matched nothing
    because rows normalize None -> 'default'."""

    def _result(self):
        return sweep(ScenarioGrid(
            workloads=("alexnet",), clusters=("k80-pcie-10gbe",),
            worker_counts=(4,), policies=("naive",),
            interconnects=(None, "ib-200g")))

    def test_filter_accepts_none(self):
        r = self._result()
        assert len(r.filter(interconnect=None)) == 1
        assert r.filter(interconnect=None) == \
            r.filter(interconnect="default")

    def test_filter_named_preset_unaffected(self):
        r = self._result()
        [row] = r.filter(interconnect="ib-200g")
        assert row["interconnect"] == "ib-200g"

    def test_label_and_row_share_normalizer(self):
        s = Scenario("alexnet", "k80-pcie-10gbe", 4, "naive")
        assert normalize_interconnect(s.interconnect) == "default"
        assert s.label().endswith("/default")
        assert _fast_eval(s)["interconnect"] == "default"
