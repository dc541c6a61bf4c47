"""JAX-native batched sweep kernels (ISSUE 6): differential agreement
with the NumPy oracle on every built-in grid and on random grids,
degenerate-scenario identities, gradient correctness against central
finite differences, sharded-mesh equivalence, and the explicit backend
routing errors."""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
from hypothesis import given, settings
from strategies import scenario_grids

from repro.core import batched_jax as BJ
from repro.core.batched import eval_scenarios, grid_evaluator
from repro.core.policies import Policy
from repro.core.scenarios import (Scenario, ScenarioGrid, default_grid,
                                  frontier_grid, mixed_grid, resolve_cluster)
from repro.core.sweep import BACKENDS, iter_rows, stream, sweep
from repro.core.workloads import resolve_workload

NUMERIC = ("iteration_time_s", "samples_per_sec", "speedup",
           "t_comm_s", "t_comp_s")
LABELS = ("workload", "cluster", "n_workers", "policy", "collective",
          "interconnect", "batch_per_gpu", "method")

TIMELINE_POLICIES = ("bucketed-1mb", "bucketed-4mb", "bucketed-25mb",
                     "bucketed-100mb", "priority")


def assert_rows_agree(jax_rows, np_rows, rel=1e-6):
    """Vectorized column-wise agreement: exact labels, <= rel numerics."""
    assert len(jax_rows) == len(np_rows) > 0
    for key in LABELS:
        assert [r[key] for r in jax_rows] == [r[key] for r in np_rows], key
    for key in NUMERIC:
        a = np.array([r[key] for r in jax_rows], dtype=np.float64)
        b = np.array([r[key] for r in np_rows], dtype=np.float64)
        np.testing.assert_allclose(a, b, rtol=rel, atol=1e-12, err_msg=key)


def assert_grid_agrees(grid, rel=1e-6):
    rj = sweep(grid, backend="jax")
    rn = sweep(grid, backend="numpy")
    assert rj.backend == "jax" and rj.n_simulated == 0
    assert rj.n_analytical == rn.n_analytical
    assert rj.n_timeline == rn.n_timeline
    assert_rows_agree(rj.rows, rn.rows, rel=rel)


class TestBuiltinGridAgreement:
    """ISSUE-6 acceptance: the jit/vmap kernels agree with the NumPy
    oracle to <= 1e-6 relative on every built-in grid (plus the
    timeline-policy variants of default/mixed)."""

    def test_default_grid(self):
        assert_grid_agrees(default_grid())

    def test_mixed_grid_spans_all_providers(self):
        g = mixed_grid()
        assert any(w.startswith("trace:") for w in g.workloads)
        assert any(w.startswith("llm:") for w in g.workloads)
        assert_grid_agrees(g)

    def test_frontier_grid(self):
        assert_grid_agrees(frontier_grid())

    def test_default_grid_bucketed_priority(self):
        assert_grid_agrees(dataclasses.replace(
            default_grid(), policies=TIMELINE_POLICIES))

    def test_eval_scenarios_jax_matches_numpy(self):
        scenarios = [
            Scenario("resnet50", "v100-nvlink-ib", 16, "caffe-mpi",
                     collective=c, interconnect=ic)
            for c in ("ring", "tree", "hierarchical")
            for ic in (None, "ib-100g@bw2@lat0.25")
        ] + [
            Scenario("trace:alexnet-k80", "k80-pcie-10gbe", 8, p)
            for p in ("naive", "bucketed-25mb", "priority")
        ] + [
            Scenario("llm:gemma3-1b", "tpu-v5e-pod", 4, "tensorflow",
                     batch_per_gpu=8),
        ]
        assert_rows_agree(BJ.eval_scenarios_jax(scenarios),
                          eval_scenarios(scenarios))


class TestRandomGridProperty:
    @settings(max_examples=10, deadline=None)
    @given(scenario_grids())
    def test_numpy_equals_jax_on_random_grids(self, grid):
        assert_grid_agrees(grid)


class TestDegenerateScenarios:
    def test_single_worker_zero_comm(self):
        """n_workers=1: no collective traffic on any backend/policy."""
        grid = ScenarioGrid(workloads=("alexnet",),
                            clusters=("k80-pcie-10gbe",), worker_counts=(1,),
                            policies=TIMELINE_POLICIES + ("caffe-mpi",))
        r = sweep(grid, backend="jax")
        for row in r.rows:
            assert row["t_comm_s"] == 0.0
            assert row["speedup"] == pytest.approx(1.0)
        times = {row["policy"]: row["iteration_time_s"] for row in r.rows}
        for name in TIMELINE_POLICIES:
            assert times[name] == pytest.approx(times["caffe-mpi"],
                                                rel=1e-12)

    def test_one_giant_bucket_equals_fused_comm_at_end(self):
        """googlenet (~28 MB of gradients) under bucketed-100mb: one
        bucket released by layer-1's backward, so the jax row must be
        max(io+h2d, comp + fused_allreduce + t_u) exactly."""
        s = Scenario("googlenet", "v100-nvlink-ib", 16, "bucketed-100mb")
        tab = resolve_workload(s.workload)
        assert float(tab.grad_bytes.sum()) < 100e6
        cluster = resolve_cluster(s)
        costs = tab.iteration_costs(cluster, tab.batch_default, 16)
        dur = cluster.allreduce_time(float(tab.grad_bytes.sum()), 16)
        want = max(costs.t_io + costs.t_h2d,
                   float(np.sum(costs.t_f) + np.sum(costs.t_b))
                   + dur + costs.t_u)
        [row] = BJ.eval_scenarios_jax([s])
        assert row["method"] == "timeline"
        assert row["iteration_time_s"] == pytest.approx(want, rel=1e-9)

    def test_one_byte_buckets_equal_per_layer_wfbp(self):
        """bucket_bytes below every layer payload ≡ caffe-mpi's exact
        per-layer closed form, on the jax backend too."""
        from repro.core import policies as P
        P.ALL_POLICIES["_bucket1b"] = Policy(
            "_bucket1b", overlap_io=True, h2d_early=True, overlap_comm=True,
            bucket_bytes=1.0)
        try:
            grid = ScenarioGrid(workloads=("alexnet", "resnet50"),
                                clusters=("v100-nvlink-ib",),
                                worker_counts=(4, 16),
                                policies=("_bucket1b", "caffe-mpi"))
            r = sweep(grid, backend="jax")
            b1 = r.filter(policy="_bucket1b")
            cm = r.filter(policy="caffe-mpi")
            assert len(b1) == len(cm) > 0
            for a, b in zip(b1, cm):
                assert a["method"] == "timeline" and b["method"] == "analytical"
                assert a["iteration_time_s"] == pytest.approx(
                    b["iteration_time_s"], rel=1e-9)
        finally:
            del P.ALL_POLICIES["_bucket1b"]


class TestGradientCorrectness:
    """jax.grad through the full kernel vs central finite differences
    on the NumPy oracle (which rebuilds bucket partitions per call)."""

    @staticmethod
    def _fd_grad(grid, p0, key, rel_eps=1e-5):
        g = np.zeros_like(p0[key])
        for i in range(g.size):
            eps = abs(float(p0[key].ravel()[i])) * rel_eps or 1e-9
            hi = {k: v.copy() for k, v in p0.items()}
            lo = {k: v.copy() for k, v in p0.items()}
            hi[key].ravel()[i] += eps
            lo[key].ravel()[i] -= eps
            g.ravel()[i] = (BJ.numpy_iteration_times(grid, hi).sum()
                            - BJ.numpy_iteration_times(grid, lo).sum()) \
                / (2 * eps)
        return g

    def _check_family(self, policies):
        grid = ScenarioGrid(workloads=("resnet50",),
                            clusters=("v100-nvlink-ib",), worker_counts=(16,),
                            policies=policies,
                            collectives=("ring", "hierarchical"))
        p0 = BJ.default_params(grid)
        got = BJ.grad_iteration_time(grid)
        # sanity: the jax path itself matches the oracle at p0
        np.testing.assert_allclose(
            np.asarray(BJ.jax_grid_evaluator(grid)
                       .columns()["iteration_time_s"]),
            BJ.numpy_iteration_times(grid), rtol=1e-9)
        for key in ("intra_bw", "intra_lat", "inter_bw", "inter_lat"):
            want = self._fd_grad(grid, p0, key)
            np.testing.assert_allclose(got[key], want, rtol=1e-3,
                                       atol=1e-12, err_msg=key)
        # at least one link parameter must actually matter
        assert any(np.abs(got[k]).max() > 0
                   for k in ("intra_bw", "inter_bw"))
        return grid, p0, got

    def test_closed_form_family(self):
        self._check_family(("caffe-mpi", "mxnet", "naive"))

    def test_timeline_family_and_flat_bucket_axis(self):
        grid, p0, got = self._check_family(
            ("bucketed-4mb", "bucketed-25mb", "priority"))
        # iteration time is piecewise constant in bucket_bytes: the
        # exact gradient is 0 a.e., and the FD twin (which *rebuilds*
        # the partition) recovers the same 0 inside a partition cell
        assert p0["bucket_bytes"].size > 0
        want = self._fd_grad(grid, p0, "bucket_bytes")
        np.testing.assert_allclose(got["bucket_bytes"], 0.0, atol=1e-12)
        np.testing.assert_allclose(want, 0.0, atol=1e-12)

    def test_unknown_param_key_rejected(self):
        f, p0 = BJ.iteration_time_fn(default_grid())
        with pytest.raises(ValueError, match="unknown param keys"):
            f({**p0, "warp_drive": np.ones(3)})


class TestShardedMesh:
    def test_explicit_mesh_matches_unsharded(self):
        import jax
        from repro.launch.mesh import make_dp_mesh

        grid = dataclasses.replace(default_grid(),
                                   worker_counts=(2, 7, 16))  # odd S: pads
        mesh = make_dp_mesh(len(jax.devices()))
        sharded = BJ.JaxGridEvaluator(grid, mesh=mesh)
        plain = BJ.JaxGridEvaluator(grid, mesh=None)
        assert sharded.mesh is mesh and plain.mesh is None
        a, b = sharded.columns(), plain.columns()
        assert set(a) == set(b)
        for k in a:
            np.testing.assert_array_equal(a[k], b[k], err_msg=k)


MC_GRID = ScenarioGrid(workloads=("resnet50",), clusters=("v100-nvlink-ib",),
                       worker_counts=(4, 16),
                       policies=("tensorflow", "bucketed-4mb", "priority"),
                       het_profiles=("het:1x0.5+3x1.0", "het:2x1.0@bw0.5"),
                       stragglers=("none", "lognormal:0.2x100"),
                       sync_ks=(0, 2))


def _per_column(args: tuple, size: int) -> dict:
    """The fetch as it was before the packed buffer: the dict-valued
    kernel, one ``np.asarray`` copy per column."""
    import jax

    with jax.enable_x64(True):
        out = BJ._columns_dict(*args)
        return {k: np.asarray(out[k])[:size] for k in BJ._NUMERIC_COLS}


def _assert_bits_equal(got: dict, want: dict):
    """Every column equal; every float64 column bit for bit."""
    assert got.keys() == want.keys()
    assert {"iteration_time_s", "t_comm_s", "t_comp_s"} <= set(want)
    for k in want:
        a, b = np.asarray(got[k]), np.asarray(want[k])
        assert a.shape == b.shape and a.dtype == b.dtype, k
        if a.dtype == np.float64:
            a, b = a.view(np.uint64), b.view(np.uint64)
        assert np.array_equal(a, b), k


def _words_of(cols: dict) -> dict:
    """The columns as the float32 words of the TPU's packing give them
    back, computed in NumPy: ``f64(hi) + f32(x - f64(hi))``."""
    out = {}
    for k, x in cols.items():
        hi = x.astype(np.float32)
        lo = (x - hi.astype(np.float64)).astype(np.float32)
        out[k] = hi.astype(np.float64) + np.where(np.isfinite(hi), lo, 0)
    return out


SHARDED_SCRIPT = textwrap.dedent("""
    import dataclasses, json
    import jax
    import numpy as np
    from repro.core import batched_jax as BJ
    from repro.core.scenarios import default_grid

    def words_of(x):
        hi = x.astype(np.float32)
        lo = (x - hi.astype(np.float64)).astype(np.float32)
        return hi.astype(np.float64) + np.where(np.isfinite(hi), lo, 0)

    def mismatched(got, want):
        return {k: int(np.sum(got[k].view(np.uint64)
                              != want[k].view(np.uint64)))
                for k in BJ._NUMERIC_COLS}

    grid = dataclasses.replace(default_grid(), worker_counts=(2, 7, 16))
    jev = BJ.JaxGridEvaluator(grid)
    S = len(jev)
    got = jev.columns()
    with jax.enable_x64(True):
        packed = BJ._columns_jax(*BJ._packed_args(jev._args()))
        words = BJ._columns_jax(*BJ._packed_args(jev._args()), shards=4)
        ref = BJ._columns_dict(*jev._args())
        want = {k: np.asarray(ref[k])[:S] for k in BJ._NUMERIC_COLS}
    BJ._f64_words = lambda: True        # the TPU's packing, on the CPU
    got_words = jev.columns()
    print("RESULT " + json.dumps({
        "devices": len(jax.devices()), "S": S,
        "mesh": None if jev.mesh is None else int(jev.mesh.devices.size),
        "packed_shape": list(packed.shape),
        "packed_devices": len(packed.sharding.device_set),
        "words_shape": list(words.shape),
        "words_devices": len(words.sharding.device_set),
        "contiguous": all(v.flags.c_contiguous for v in got.values()),
        "mismatched": mismatched(got, want),
        "words_mismatched": mismatched(
            got_words, {k: words_of(v) for k, v in want.items()})}))
""")


class TestPackedFetch:
    """The six numeric columns come back from the device in one packed
    buffer, bit for bit what one ``np.asarray`` per column gave."""

    @pytest.mark.parametrize("path", ["frontier", "monte_carlo",
                                      "scenario_list"])
    def test_packed_fetch_is_bit_identical_to_per_column_copies(
            self, path, monkeypatch):
        if path == "frontier":
            jev = BJ.jax_grid_evaluator(frontier_grid())
            got = jev.columns()
            assert all(v.flags.c_contiguous for v in got.values())
            _assert_bits_equal(got, _per_column(jev._args(), len(jev)))
            return
        if path == "monte_carlo":
            def evaluate():
                table, _ = BJ.jax_grid_evaluator(MC_GRID).run(seed=5) \
                    .table_slice(0, len(MC_GRID))
                return table
        else:
            scenarios = list(MC_GRID)[::3]

            def evaluate():
                return BJ.eval_scenarios_table_jax(scenarios, seed=5)
        got = evaluate()
        monkeypatch.setattr(BJ, "_host_columns", _per_column)
        want = evaluate()
        assert not np.array_equal(want["t_p99_s"], want["iteration_time_s"])
        _assert_bits_equal(got, want)

    def test_packed_fetch_is_bit_identical_on_four_sharded_devices(self):
        src = str(Path(__file__).resolve().parents[1] / "src")
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        r = subprocess.run([sys.executable, "-c", SHARDED_SCRIPT], env=env,
                           capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-3000:]
        line = [x for x in r.stdout.splitlines() if x.startswith("RESULT ")]
        got = json.loads(line[0][len("RESULT "):])
        assert got["devices"] == got["mesh"] == got["packed_devices"] == 4
        assert got["words_devices"] == 4
        assert got["S"] % 4                                  # padded
        padded = got["S"] + (-got["S"]) % 4
        assert got["packed_shape"] == [len(BJ._NUMERIC_COLS), padded]
        block = padded // 4 + (-(padded // 4)) % BJ._TILE
        assert got["words_shape"] == [4, 2 * len(BJ._NUMERIC_COLS) * block]
        assert got["contiguous"]
        assert got["mismatched"] == {k: 0 for k in BJ._NUMERIC_COLS}
        assert got["words_mismatched"] == {k: 0 for k in BJ._NUMERIC_COLS}

    def test_the_tpu_word_packing_gives_back_the_columns_words(
            self, monkeypatch):
        """The TPU's packing, run on the CPU: its layout and unpacking
        give back each column's two float32 words added in float64
        (exact on the TPU, whose float64 is those words; on the CPU's
        IEEE float64 the words drop the low bits)."""
        jev = BJ.jax_grid_evaluator(frontier_grid())
        want = _words_of(_per_column(jev._args(), len(jev)))
        monkeypatch.setattr(BJ, "_f64_words", lambda: True)
        got = jev.columns()
        assert all(v.flags.c_contiguous for v in got.values())
        _assert_bits_equal(got, want)

    def test_float32_words_rebuild_every_value_a_pair_holds(self):
        """The words form (the TPU's) gives back, exactly, any float64
        whose significand fits two float32 words, and the infinities."""
        import jax

        r = np.random.default_rng(3).lognormal(0.0, 8.0, 4096)
        m, e = np.frexp(r)
        x = np.ldexp(np.round(np.ldexp(m, 48)), e - 48)      # 48 bits
        x = np.concatenate([x, -x, [0.0, 1.0, np.inf, -np.inf]])
        with jax.enable_x64(True):
            hi, lo = (np.asarray(w) for w in jax.jit(BJ._f32_words)(x))
        assert hi.dtype == lo.dtype == np.float32
        assert np.all(lo[~np.isfinite(x)] == 0)
        back = hi.astype(np.float64) + lo
        assert np.array_equal(back.view(np.uint64), x.view(np.uint64))


def _links(bw=(0.5, 1, 2, 4), lat=(0.25, 1, 4)) -> tuple:
    return tuple(f"{base}@bw{b:g}@lat{a:g}"
                 for base in ("10gbe", "ib-100g", "ib-100g-fused", "ib-200g")
                 for b in bw for a in lat)


def _dsv2_lite_ep_grid() -> ScenarioGrid:
    """The grid of the benchmark cell ``dsv2_lite_ep.sweep``."""
    from repro.core.hardware import COLLECTIVE_ALGORITHMS
    from repro.core.scenarios import FRONTIER_POLICIES

    return ScenarioGrid(workloads=("llm:deepseek-v2-lite",),
                        clusters=("v100-nvlink-ib", "tpu-v5e-pod"),
                        worker_counts=(64, 128, 256, 512),
                        ep_sizes=(1, 2, 4, 8, 16, 32, 64),
                        policies=FRONTIER_POLICIES,
                        collectives=COLLECTIVE_ALGORITHMS,
                        interconnects=_links())


def _moe_grid(ep_sizes) -> ScenarioGrid:
    return ScenarioGrid(workloads=("llm:qwen2-moe-a2.7b",),
                        clusters=("v100-nvlink-ib",), worker_counts=(4, 8),
                        ep_sizes=ep_sizes,
                        policies=("tensorflow", "bucketed-25mb"),
                        collectives=("ring", "hierarchical"))


#: Heterogeneous workers, stragglers, K-of-N sync and faults together.
HET_FAULT_GRID = dataclasses.replace(
    MC_GRID, faults=(None, "fail:0.1@restart1.5x16"))

PACKED_GRIDS = {
    "frontier": frontier_grid,
    "dsv2_lite_ep": _dsv2_lite_ep_grid,
    "moe_ep1": lambda: _moe_grid((1,)),
    "moe_ep124": lambda: _moe_grid((1, 2, 4)),
    "het_straggler_fault_kofn": lambda: HET_FAULT_GRID,
    "mixed": mixed_grid,
}


class TestPackedInputs:
    """The kernel's host inputs cross to the device as two packed
    buffers (float64 tables, int32 flags and codes), and the columns
    are bit for bit those of the dict-valued kernel on the dicts."""

    @pytest.mark.parametrize("name", list(PACKED_GRIDS))
    def test_packed_call_is_bit_identical_to_the_dict_kernel(self, name):
        jev = BJ.JaxGridEvaluator(PACKED_GRIDS[name]())
        f64, i32, live, layout, *_ = BJ._packed_args(jev._args())
        assert f64.dtype == np.float64 and i32.dtype == np.int32
        assert live == ()
        kinds = {kind for group in layout for _, kind, _, _ in group}
        assert kinds == {"f", "i", "b"}
        assert ("we" in jev._kcodes) == (name in ("dsv2_lite_ep",
                                                  "moe_ep124"))
        got = jev.columns()
        _assert_bits_equal(got, _per_column(jev._args(), len(jev)))

    def test_scenario_list_is_bit_identical_to_the_dict_kernel(
            self, monkeypatch):
        scenarios = list(HET_FAULT_GRID)[::5] + list(_moe_grid((1, 2, 4)))
        got = BJ.eval_scenarios_table_jax(scenarios, seed=3)
        monkeypatch.setattr(BJ, "_host_columns", _per_column)
        want = BJ.eval_scenarios_table_jax(scenarios, seed=3)
        _assert_bits_equal(got, want)

    def test_the_unpacked_dicts_are_the_arguments(self):
        import jax

        jev = BJ.JaxGridEvaluator(_moe_grid((1, 2, 4)))
        args = jev._args()
        f64, i32, live, layout, *_ = BJ._packed_args(args)
        with jax.enable_x64(True):
            back = jax.jit(BJ._unpacked_args, static_argnums=3)(
                f64, i32, live, layout)
        for want, got in zip(args[:5], back):
            assert want.keys() == got.keys()
            for k, v in want.items():
                g = np.asarray(got[k])
                assert g.shape == v.shape, k
                assert g.dtype == (np.float64 if v.dtype.kind == "f"
                                   else bool if v.dtype.kind == "b"
                                   else np.int32), k
                assert np.array_equal(g, v), k

    def test_a_same_shaped_grid_reuses_the_executable(self):
        """A fresh frontier of the same shape (other link factors) hits
        the executable the first one compiled: the layout depends only
        on keys and shapes."""
        first = frontier_grid()
        other = dataclasses.replace(first, interconnects=_links(
            bw=(0.75, 1.5, 3, 6), lat=(0.5, 2, 8)))
        BJ.JaxGridEvaluator(first).columns()
        size = BJ._columns_jax._cache_size()
        a = BJ._packed_args(BJ.JaxGridEvaluator(first)._args())
        b = BJ._packed_args(BJ.JaxGridEvaluator(other)._args())
        assert a[3] == b[3] and not np.array_equal(a[0], b[0])
        BJ.JaxGridEvaluator(other).columns()
        assert BJ._columns_jax._cache_size() == size

    @pytest.mark.parametrize("value", [2**31, -2**31 - 1])
    def test_an_integer_beyond_int32_raises_naming_the_leaf(self, value):
        jev = BJ.JaxGridEvaluator(default_grid())
        tables, pflags, kcodes, *rest = jev._args()
        n = kcodes["n"].copy()
        n[3] = value
        with pytest.raises(ValueError, match=r"kcodes\['n'\].*int32"):
            BJ._packed_args((tables, pflags, {**kcodes, "n": n}, *rest))
        n[3] = 2**31 - 1                            # the largest that fits
        BJ._packed_args((tables, pflags, {**kcodes, "n": n}, *rest))


class TestBackendRouting:
    """Satellite 4: invalid backend combinations raise loudly — the jax
    backend never falls back to a NumPy path silently."""

    def test_unknown_backend(self):
        for fn in (lambda: sweep(default_grid(), backend="torch"),
                   lambda: list(iter_rows(default_grid(), backend="torch"))):
            with pytest.raises(ValueError, match="unknown backend"):
                fn()
        assert "jax" in BACKENDS and "numpy" in BACKENDS

    def test_jax_rejects_batched_false(self):
        with pytest.raises(ValueError, match="batched=False"):
            sweep(default_grid(), backend="jax", batched=False)
        with pytest.raises(ValueError, match="batched=False"):
            list(iter_rows(default_grid(), backend="jax", batched=False))

    def test_jax_rejects_force_simulator(self):
        with pytest.raises(ValueError, match="force_simulator"):
            sweep(default_grid(), backend="jax", force_simulator=True)
        with pytest.raises(ValueError, match="force_simulator"):
            stream(default_grid(), json_path="/dev/null", backend="jax",
                   force_simulator=True)

    def test_jax_rejects_simulator_only_policies(self):
        from repro.core import policies as P
        # unstudied flag combination: neither closed nor timeline form
        P.ALL_POLICIES["_simonly"] = Policy(
            "_simonly", overlap_io=False, overlap_comm=True,
            bucket_bytes=25e6)
        try:
            grid = ScenarioGrid(workloads=("alexnet",),
                                clusters=("v100-nvlink-ib",),
                                worker_counts=(2,),
                                policies=("caffe-mpi", "_simonly"))
            with pytest.raises(ValueError, match="_simonly"):
                sweep(grid, backend="jax")
            with pytest.raises(ValueError, match="_simonly"):
                BJ.eval_scenarios_jax(grid.expand())
            # the NumPy backend happily interleaves the simulator
            r = sweep(grid, backend="numpy")
            assert r.n_simulated == 1 and r.backend == "numpy"
        finally:
            del P.ALL_POLICIES["_simonly"]

    def test_stream_json_carries_backend(self, tmp_path):
        path = tmp_path / "s.json"
        summary = stream(ScenarioGrid(workloads=("alexnet",),
                                      worker_counts=(2,)),
                         json_path=str(path), backend="jax")
        assert summary["backend"] == "jax"
        doc = json.loads(path.read_text())
        assert doc["backend"] == "jax"
        assert doc["n_simulated"] == 0

    def test_sweep_result_json_carries_backend(self, tmp_path):
        r = sweep(ScenarioGrid(workloads=("alexnet",), worker_counts=(2,)),
                  backend="jax")
        path = tmp_path / "r.json"
        r.to_json(str(path))
        assert json.loads(path.read_text())["backend"] == "jax"


class TestKernelSurface:
    def test_columns_slice_matches_numpy_gridrun(self):
        """The kernel-only surfaces the benchmark times are comparable:
        jax JaxGridRun.columns_slice vs NumPy GridRun.columns_slice."""
        grid = default_grid()
        jr = BJ.jax_grid_evaluator(grid).run()
        nr = grid_evaluator(grid).run()
        a = jr.columns_slice(7, 203)
        b = nr.columns_slice(7, 203)
        for k in NUMERIC:
            np.testing.assert_allclose(a[k], b[k], rtol=1e-6, err_msg=k)
        assert a["method"] == ["analytical"] * (203 - 7)

    def test_empty_grid_columns(self):
        grid = dataclasses.replace(default_grid(), worker_counts=())
        jev = BJ.JaxGridEvaluator(grid)
        cols = jev.columns()
        assert all(v.size == 0 for v in cols.values())
