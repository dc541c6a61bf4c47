"""The measurement loop: segmentation math, calibration fits, the
``jax:`` workload provider, the hostdev flag helper, and (slow, in a
subprocess with forced host devices) the end-to-end instrumented run
with its bytes cross-check — lowered ``wfbp`` HLO collective bytes
must equal the matching workload table's ``sum(grad_bytes)``, tying
``comm/sync.py``, ``launch/hlo.py`` and ``core/workloads.py``
together."""
import math
import os
from pathlib import Path

import numpy as np
import pytest

from repro.core.dag import IterationCosts
from repro.core.predictor import (SYNC_POLICY_MODELS, predict_sync_policy,
                                  predict_workload)
from repro.core.policies import CAFFE_MPI, get_policy
from repro.core.scenarios import ScenarioGrid
from repro.core.sweep import sweep
from repro.core.workloads import (clear_workload_cache, known_workloads,
                                  resolve_workload)
from repro.launch.hostdev import (HOST_DEVICE_FLAG, child_env,
                                  force_host_device_count,
                                  host_device_flags)
from repro.measure.calibrate import (METRIC_COLLECTIVE_BYTES, fit_alpha_beta,
                                     comm_scale_from_fit)
from repro.measure.harness import segment_from_depths
from repro.traces.format import make_trace, read_trace, write_trace

SRC = str(Path(__file__).resolve().parents[1] / "src")


# ----------------------------------------------------------------------
# hostdev: the shared XLA_FLAGS helper (the dry-run clobber fix)
# ----------------------------------------------------------------------
class TestHostdev:
    def test_fresh_env(self):
        assert host_device_flags(8) == f"{HOST_DEVICE_FLAG}=8"

    def test_preserves_user_flags(self):
        out = host_device_flags(8, "--xla_cpu_enable_fast_math=false")
        assert "--xla_cpu_enable_fast_math=false" in out
        assert out.endswith(f"{HOST_DEVICE_FLAG}=8")

    def test_replaces_existing_count_idempotently(self):
        once = host_device_flags(8, f"--foo=1 {HOST_DEVICE_FLAG}=2")
        again = host_device_flags(8, once)
        assert once == again == f"--foo=1 {HOST_DEVICE_FLAG}=8"

    def test_force_applies_to_env(self):
        env = {"XLA_FLAGS": "--bar=2"}
        value = force_host_device_count(4, env)
        assert env["XLA_FLAGS"] == value
        assert "--bar=2" in value and f"{HOST_DEVICE_FLAG}=4" in value

    def test_child_env_copies(self):
        env = child_env(4, {"PYTHONPATH": "x"})
        assert env["PYTHONPATH"] == "x"
        assert f"{HOST_DEVICE_FLAG}=4" in env["XLA_FLAGS"]
        assert env["JAX_PLATFORMS"] == "cpu"

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            host_device_flags(0)


# ----------------------------------------------------------------------
# The persistent compilation cache's location rule
# ----------------------------------------------------------------------
class TestCompileCacheLocation:
    def test_env_var_leaves_the_location_to_jax(self):
        from repro.launch.compile_cache import compilation_cache_dir

        assert compilation_cache_dir(
            {"JAX_COMPILATION_CACHE_DIR": "/elsewhere"}) is None

    def test_default_is_the_checkouts_ignored_dir(self):
        from repro.launch.compile_cache import (CHECKOUT_CACHE_DIR,
                                                compilation_cache_dir)

        root = Path(__file__).resolve().parents[1]
        assert compilation_cache_dir({}) == CHECKOUT_CACHE_DIR \
            == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()

    def test_cache_is_written_where_the_env_var_says(self, tmp_path):
        import subprocess
        import sys

        code = ("from repro.launch.compile_cache import "
                "enable_compilation_cache as e; print(e()); import jax; "
                "jax.jit(lambda x: x * 3)(1.0).block_until_ready()")
        env = child_env(1)
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
        env["PYTHONPATH"] = SRC
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, check=True)
        assert out.stdout.strip() == str(tmp_path)
        assert any(tmp_path.iterdir())


# ----------------------------------------------------------------------
# Scan-structure segmentation (pure math)
# ----------------------------------------------------------------------
class TestSegmentation:
    def test_exact_recovery_from_linear_data(self):
        # fwd = 0.5 + 0.2*u ; full = 0.8 + 0.7*u  (=> bwd 0.3 + 0.5*u)
        units = [2, 4, 8]
        fwd = [0.5 + 0.2 * u for u in units]
        full = [0.8 + 0.7 * u for u in units]
        seg = segment_from_depths(units, fwd, full)
        assert seg.unit_fwd_s == pytest.approx(0.2)
        assert seg.unit_bwd_s == pytest.approx(0.5)
        assert seg.rest_fwd_s == pytest.approx(0.5)
        assert seg.rest_bwd_s == pytest.approx(0.3)

    def test_noise_clamps_to_zero(self):
        # full < fwd (impossible physically, pure noise): bwd clamps to 0
        seg = segment_from_depths([1, 2], [1.0, 2.0], [0.9, 1.8])
        assert seg.unit_bwd_s == 0.0
        assert seg.rest_bwd_s == pytest.approx(0.0, abs=1e-12)

    def test_requires_two_distinct_depths(self):
        with pytest.raises(ValueError):
            segment_from_depths([3], [1.0], [2.0])
        with pytest.raises(ValueError):
            segment_from_depths([3, 3], [1.0, 1.0], [2.0, 2.0])


# ----------------------------------------------------------------------
# Alpha-beta calibration fit
# ----------------------------------------------------------------------
class TestAlphaBetaFit:
    def test_exact_two_point_fit(self):
        alpha, bw = 2e-4, 5e9
        samples = [(1e6, alpha + 1e6 / bw), (1e8, alpha + 1e8 / bw)]
        lat, fit_bw = fit_alpha_beta(samples)
        assert lat == pytest.approx(alpha, rel=1e-9)
        assert fit_bw == pytest.approx(bw, rel=1e-9)

    def test_no_samples_means_no_comm(self):
        lat, bw = fit_alpha_beta([])
        assert lat == 0.0 and math.isinf(bw)
        assert comm_scale_from_fit(lat, bw)(1e9, 0.0) == 0.0

    def test_single_sample_pins_latency_to_zero(self):
        lat, bw = fit_alpha_beta([(1e6, 1e-3)])
        assert lat == 0.0
        assert bw == pytest.approx(1e9)

    def test_repeated_payloads_collapse_to_their_minimum(self):
        # noisy repeats of one payload: an outlier-first ordering must
        # not decide the fit — the minimum observation does
        lat, bw = fit_alpha_beta([(1e6, 9e-3), (1e6, 1e-3), (1e6, 2e-3)])
        assert lat == 0.0
        assert bw == pytest.approx(1e9)
        alpha, beta = 2e-4, 5e9
        samples = [(1e6, alpha + 1e6 / beta + 5e-3),     # outlier
                   (1e6, alpha + 1e6 / beta),
                   (1e8, alpha + 1e8 / beta)]
        lat, bw = fit_alpha_beta(samples)
        assert lat == pytest.approx(alpha, rel=1e-9)
        assert bw == pytest.approx(beta, rel=1e-9)

    def test_negative_slope_degrades_to_infinite_bandwidth(self):
        lat, bw = fit_alpha_beta([(1e6, 2e-3), (2e6, 1e-3)])
        assert math.isinf(bw)

    def test_comm_scale_zero_payload(self):
        scale = comm_scale_from_fit(1e-4, 1e9)
        assert scale(0.0, 123.0) == 0.0
        assert scale(1e9, 0.0) == pytest.approx(1e-4 + 1.0)


# ----------------------------------------------------------------------
# Payload accounting across mixed parameter dtypes
# ----------------------------------------------------------------------
class TestExpectedCollectiveBytes:
    def test_per_leaf_accounting_with_mixed_dtypes(self):
        """bf16 configs keep f32 leaves (norms): the bucketed (f32
        upcast) expectation must count 4 bytes per *element*, and the
        at_end/wfbp one each leaf's own dtype — rescaling a
        dtype-weighted total would miscount the mix."""
        import jax
        import jax.numpy as jnp

        from repro.configs import get_config
        from repro.measure.calibrate import expected_collective_bytes
        from repro.models import transformer as T

        cfg = get_config("qwen1.5-4b").reduced(
            num_layers=2, d_model=64, num_heads=4, d_ff=128,
            vocab_size=256, dtype=jnp.bfloat16)
        leaves = jax.tree_util.tree_leaves(
            jax.eval_shape(lambda k: T.init_lm(cfg, k),
                           jax.random.PRNGKey(0)))
        n_elems = sum(l.size for l in leaves)
        dtype_bytes = sum(l.size * jnp.dtype(l.dtype).itemsize
                          for l in leaves)
        assert expected_collective_bytes(cfg, "bucketed") \
            == 4.0 * n_elems + METRIC_COLLECTIVE_BYTES
        assert expected_collective_bytes(cfg, "wfbp") \
            == dtype_bytes + METRIC_COLLECTIVE_BYTES
        assert expected_collective_bytes(cfg, "at_end") \
            == dtype_bytes + METRIC_COLLECTIVE_BYTES


# ----------------------------------------------------------------------
# Runner geometry flags
# ----------------------------------------------------------------------
class TestRunnerGeometry:
    def test_smoke_preset_applies_when_flags_untouched(self):
        from repro.measure.run import (SMOKE_GEOMETRY, _geometry_from_args,
                                       build_parser)

        args = build_parser().parse_args(["--arch", "gemma3-1b", "--smoke"])
        assert _geometry_from_args(args) == SMOKE_GEOMETRY

    def test_explicit_flag_wins_even_when_equal_to_full_default(self):
        from repro.measure.run import (Geometry, SMOKE_GEOMETRY,
                                       _geometry_from_args, build_parser)

        full = Geometry()
        args = build_parser().parse_args(
            ["--arch", "gemma3-1b", "--smoke",
             "--seq-len", str(full.seq_len)])
        g = _geometry_from_args(args)
        assert g.seq_len == full.seq_len          # explicit value kept
        assert g.num_layers == SMOKE_GEOMETRY.num_layers  # preset rest

    def test_every_geometry_field_has_a_parser_flag(self):
        import dataclasses

        from repro.measure.run import Geometry, _geometry_flag, build_parser

        parser = build_parser()
        argv = ["--arch", "gemma3-1b"]
        for i, f in enumerate(dataclasses.fields(Geometry)):
            argv += [_geometry_flag(f.name), str(100 + i)]
        args = parser.parse_args(argv)
        for i, f in enumerate(dataclasses.fields(Geometry)):
            assert getattr(args, f.name) == 100 + i


# ----------------------------------------------------------------------
# Sync-policy prediction mapping
# ----------------------------------------------------------------------
class TestPredictSyncPolicy:
    costs = IterationCosts(
        t_f=[0.01, 0.02, 0.03], t_b=[0.02, 0.04, 0.06],
        t_c=[0.005, 0.01, 0.015], t_io=0.0, t_h2d=0.0, t_u=0.007,
        grad_bytes=[1e6, 2e6, 3e6])

    def test_at_end_is_one_fused_collective_after_backward(self):
        scale = comm_scale_from_fit(1e-3, 1e9)
        t = predict_sync_policy(self.costs, 4, "at_end", comm_scale=scale)
        serial = sum(self.costs.t_f) + sum(self.costs.t_b)
        expected = serial + scale(6e6, 0.0) + self.costs.t_u
        assert t == pytest.approx(expected, rel=1e-9)

    def test_wfbp_matches_caffe_mpi_policy(self):
        from repro.core.simulator import simulate_steady

        t = predict_sync_policy(self.costs, 4, "wfbp")
        assert t == pytest.approx(
            simulate_steady(self.costs, 4, CAFFE_MPI, n_iterations=8),
            rel=1e-9)

    def test_bucketed_threshold_override(self):
        scale = comm_scale_from_fit(1e-3, 1e9)
        # tiny threshold -> per-layer buckets; giant -> one fused bucket
        t_small = predict_sync_policy(self.costs, 4, "bucketed",
                                      comm_scale=scale, bucket_bytes=1.0)
        t_fused = predict_sync_policy(self.costs, 4, "bucketed",
                                      comm_scale=scale, bucket_bytes=1e12)
        t_at_end = predict_sync_policy(self.costs, 4, "at_end",
                                       comm_scale=scale)
        assert t_fused == pytest.approx(t_at_end, rel=1e-9)
        assert t_small != pytest.approx(t_fused, rel=1e-6)

    def test_unknown_policy_raises(self):
        with pytest.raises(ValueError, match="unknown sync policy"):
            predict_sync_policy(self.costs, 4, "gossip")

    def test_model_table_is_exhaustive_over_sync_policies(self):
        from repro.comm.sync import SYNC_POLICIES

        assert set(SYNC_POLICY_MODELS) == set(SYNC_POLICIES) - {"none"}


# ----------------------------------------------------------------------
# Trace-format bytes-per-sample header
# ----------------------------------------------------------------------
class TestBytesPerSampleHeader:
    def test_round_trip(self, tmp_path):
        tr = make_trace("net", "clu",
                        [(0, "embed", 10.0, 20.0, 0.0, 4096.0),
                         (1, "unit0", 5.0, 9.0, 0.0, 2048.0)],
                        batch_per_gpu=4, bytes_per_sample=256.0)
        p = tmp_path / "t.trace"
        write_trace(tr, p)
        assert "# bytes-per-sample: 256" in p.read_text()
        back = read_trace(p)
        assert back == tr

    def test_absent_header_means_zero(self, tmp_path):
        tr = make_trace("net", "clu", [(0, "l", 1.0, 2.0, 0.0, 8.0)])
        p = tmp_path / "t.trace"
        write_trace(tr, p)
        assert "bytes-per-sample" not in p.read_text()
        assert read_trace(p).bytes_per_sample == 0.0

    def test_malformed_header_raises(self, tmp_path):
        p = tmp_path / "t.trace"
        p.write_text("# bytes-per-sample: lots\n0\tl\t1\t2\t0\t8\n")
        with pytest.raises(ValueError, match="bytes-per-sample"):
            read_trace(p)


# ----------------------------------------------------------------------
# jax: workload provider
# ----------------------------------------------------------------------
@pytest.fixture
def measured_dir(tmp_path, monkeypatch):
    """A measurement directory with one synthetic measured trace, wired
    in as $REPRO_MEASURE_DIR."""
    tr = make_trace("tiny-lm", "jax-host-cpu-x2",
                    [(0, "embed_head", 120.0, 260.0, 0.0, 524800.0),
                     (1, "unit0", 900.0, 1800.0, 0.0, 657920.0),
                     (2, "unit1", 900.0, 1800.0, 0.0, 657920.0)],
                    batch_per_gpu=2, bytes_per_sample=256.0)
    write_trace(tr, tmp_path / "tiny-lm.trace")
    monkeypatch.setenv("REPRO_MEASURE_DIR", str(tmp_path))
    clear_workload_cache()
    yield tmp_path
    clear_workload_cache()


class TestJaxProvider:
    def test_listed_in_known_workloads(self, measured_dir):
        assert "jax:tiny-lm" in known_workloads()

    def test_resolves_to_measured_table(self, measured_dir):
        tab = resolve_workload("jax:tiny-lm")
        assert tab.is_measured
        assert tab.name == "jax:tiny-lm"
        assert tab.num_layers == 3
        assert tab.bytes_per_sample == 256.0
        assert tab.batch_default == 2
        np.testing.assert_allclose(
            tab.grad_bytes, [524800.0, 657920.0, 657920.0])

    def test_resolves_explicit_path(self, measured_dir):
        path = str(measured_dir / "tiny-lm.trace")
        tab = resolve_workload(f"jax:{path}")
        assert tab.is_measured and tab.num_layers == 3

    def test_unknown_spec_mentions_the_measure_cli(self, measured_dir):
        with pytest.raises(ValueError, match="repro.measure"):
            resolve_workload("jax:never-measured")

    def test_predict_workload(self, measured_dir):
        from repro.core.hardware import CLUSTERS

        p = predict_workload("jax:tiny-lm", CLUSTERS["v100-nvlink-ib"],
                             8, CAFFE_MPI)
        assert p.iteration_time > 0
        assert 0 < p.speedup <= 8.0

    def test_sweeps_through_batched_engine_both_paths(self, measured_dir):
        """Closed-form AND bucket-timeline batched paths serve jax:
        workloads, and both agree with the event-driven oracle."""
        grid = ScenarioGrid(
            workloads=("jax:tiny-lm",),
            clusters=("k80-pcie-10gbe", "v100-nvlink-ib"),
            worker_counts=(2, 8),
            policies=("cntk", "caffe-mpi", "bucketed-25mb", "priority"),
            collectives=("ring",))
        fast = sweep(grid)
        assert fast.n_analytical == 8 and fast.n_timeline == 8 \
            and fast.n_simulated == 0
        oracle = sweep(grid, force_simulator=True)
        for rf, ro in zip(fast.rows, oracle.rows):
            assert rf["iteration_time_s"] == pytest.approx(
                ro["iteration_time_s"], rel=1e-6), rf

    def test_stale_cache_busted_on_rewrite(self, measured_dir):
        t1 = resolve_workload("jax:tiny-lm")
        tr = make_trace("tiny-lm", "jax-host-cpu-x2",
                        [(0, "embed_head", 50.0, 90.0, 0.0, 1000.0)],
                        batch_per_gpu=2)
        path = measured_dir / "tiny-lm.trace"
        write_trace(tr, path)
        os.utime(path, ns=(1, 1))   # force a distinct mtime
        t2 = resolve_workload("jax:tiny-lm")
        assert t2.num_layers == 1 and t1.num_layers == 3


# ----------------------------------------------------------------------
# End to end, in a forced-host-device subprocess (slow): measure a tiny
# model, then cross-check HLO collective bytes against the jax: table.
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def measured_run(tmp_path_factory):
    from repro.measure.run import Geometry, measure_in_subprocess

    out = tmp_path_factory.mktemp("measure")
    # repeats=5 + seq_len=32 keep the segmentation slope (min-of-
    # repeats at a 2x depth spread) robustly above wall-clock noise
    # even on a loaded 2-core box; compile time dominates the cost
    geometry = Geometry(num_layers=2, d_model=64, num_heads=4, d_ff=128,
                        vocab_size=256, seq_len=32, batch_per_gpu=2,
                        n_devices=2, repeats=5, step_iters=3)
    doc = measure_in_subprocess("qwen1.5-4b", out_dir=out,
                                geometry=geometry, timeout=560)
    return out, doc


class TestMeasuredRunEndToEnd:
    def test_artifacts_and_sanity(self, measured_run):
        out, doc = measured_run
        assert (out / "qwen1.5-4b.trace").exists()
        for pol in ("at_end", "wfbp", "bucketed"):
            assert doc["policy_times_s"][pol] > 0
        assert doc["t_update_s"] > 0
        assert doc["allreduce_fit"]["bandwidth_bytes_per_s"] > 0
        assert len(doc["allreduce_samples"]) >= 2

    def test_wfbp_hlo_bytes_equal_table_grad_bytes(self, measured_run,
                                                   monkeypatch):
        """The satellite cross-check: the lowered wfbp step's
        while-loop-scaled HLO collective bytes equal the matching
        workload table's sum(grad_bytes) (plus the two scalar metric
        pmeans) — drift in comm/sync.py, launch/hlo.py or the table
        construction breaks this equality."""
        out, doc = measured_run
        monkeypatch.setenv("REPRO_MEASURE_DIR", str(out))
        clear_workload_cache()
        tab = resolve_workload("jax:qwen1.5-4b")
        table_bytes = float(np.sum(tab.grad_bytes))
        hlo_bytes = doc["collective_stats"]["wfbp"]["total_bytes"]
        assert hlo_bytes == pytest.approx(
            table_bytes + METRIC_COLLECTIVE_BYTES, rel=1e-9)
        # and the harness's own cross-check agreed, for every policy
        for pol, chk in doc["bytes_crosscheck"].items():
            assert chk["rel_err"] < 1e-6, (pol, chk)
        clear_workload_cache()

    def test_trace_segments_are_positive(self, measured_run):
        out, _ = measured_run
        trace = read_trace(out / "qwen1.5-4b.trace")
        recs = trace.iterations[0]
        assert [r.name for r in recs][:2] == ["embed_head", "unit0"]
        assert all(r.size_bytes > 0 for r in recs)
        assert all(r.forward_us >= 0 and r.backward_us >= 0 for r in recs)
        # unit compute must be non-degenerate (the scan slope)
        assert recs[1].forward_us > 0 and recs[1].backward_us > 0

    def test_predictions_are_finite_and_close(self, measured_run):
        """The Fig.-4 loop on the measured doc: model predictions for
        every policy are finite, positive and within a (generous,
        CPU-noise-proof) factor of the measurement."""
        from benchmarks.bench_model_vs_measured import predict_policies

        out, doc = measured_run
        preds = predict_policies(doc, str(out / "qwen1.5-4b.trace"))
        for pol, t_pred in preds.items():
            t_meas = doc["policy_times_s"][pol]
            assert math.isfinite(t_pred) and t_pred > 0
            assert t_pred / t_meas < 10 and t_meas / t_pred < 10, \
                (pol, t_pred, t_meas)
