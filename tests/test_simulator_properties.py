"""Hypothesis property tests for the event-driven simulator and the
launch-layer spec builders."""
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import SHAPES, dryrun_matrix, get_config, shape_applies
from repro.core.dag import DAG, IterationCosts, TaskKind, build_ssgd_dag
from repro.core.policies import ALL_POLICIES
from repro.core.simulator import simulate


@st.composite
def random_costs(draw, max_layers=6):
    L = draw(st.integers(1, max_layers))
    pos = st.floats(0.01, 10.0)
    return IterationCosts(
        t_f=draw(st.lists(pos, min_size=L, max_size=L)),
        t_b=draw(st.lists(pos, min_size=L, max_size=L)),
        t_c=draw(st.lists(pos, min_size=L, max_size=L)),
        t_io=draw(pos), t_h2d=draw(pos), t_u=draw(pos))


class TestSimulatorInvariants:
    @settings(max_examples=50, deadline=None)
    @given(random_costs(), st.integers(1, 4),
           st.sampled_from(sorted(ALL_POLICIES)))
    def test_bounds(self, costs, n_workers, polname):
        pol = ALL_POLICIES[polname]
        g = build_ssgd_dag(costs, n_workers, pol, n_iterations=2)
        r = simulate(g)
        cp, _ = g.critical_path()
        # resource-constrained makespan is bounded below by the
        # critical path and above by full serialization
        assert r.makespan >= cp - 1e-9
        assert r.makespan <= g.total_work() + 1e-9
        for ch, busy in r.channel_busy.items():
            assert busy <= r.makespan + 1e-9          # utilization <= 1

    @settings(max_examples=50, deadline=None)
    @given(random_costs(), st.integers(2, 4),
           st.sampled_from(sorted(ALL_POLICIES)))
    def test_precedence_respected(self, costs, n_workers, polname):
        pol = ALL_POLICIES[polname]
        g = build_ssgd_dag(costs, n_workers, pol, n_iterations=2)
        r = simulate(g)
        for tid, preds in g.preds.items():
            for p in preds:
                assert r.schedule[p].finish <= r.schedule[tid].start + 1e-9

    @settings(max_examples=30, deadline=None)
    @given(random_costs(), st.integers(2, 4))
    def test_channel_exclusive(self, costs, n_workers):
        g = build_ssgd_dag(costs, n_workers, ALL_POLICIES["caffe-mpi"],
                           n_iterations=2)
        r = simulate(g)
        by_ch: dict = {}
        for s in r.schedule.values():
            by_ch.setdefault(s.task.channel, []).append(s)
        for items in by_ch.values():
            items.sort(key=lambda s: s.start)
            for a, b in zip(items, items[1:]):
                assert a.finish <= b.start + 1e-9


class TestInputSpecs:
    def test_matrix_size(self):
        m = dryrun_matrix()
        assert len(m) == 36          # 11*3 + 3 long_500k
        assert ("internlm2-20b", "long_500k") not in m
        assert ("rwkv6-1.6b", "long_500k") in m

    @pytest.mark.parametrize("arch,shape", [
        ("internlm2-20b", "train_4k"), ("whisper-tiny", "train_4k"),
        ("llama-3.2-vision-90b", "prefill_32k"),
        ("rwkv6-1.6b", "decode_32k"), ("gemma3-1b", "long_500k")])
    def test_specs_shapes(self, arch, shape):
        from repro.launch.steps import input_specs
        cfg = get_config(arch)
        sh = SHAPES[shape]
        specs = input_specs(cfg, sh)
        if sh.kind == "train":
            assert specs["tokens"].shape == (sh.global_batch, sh.seq_len)
            if cfg.arch_type == "audio":
                assert specs["frames"].shape == (sh.global_batch,
                                                 cfg.encoder_seq, cfg.d_model)
            if cfg.arch_type == "vlm":
                assert specs["images"].shape[1] == cfg.num_image_tokens
        elif sh.kind == "decode":
            assert specs["token"].shape == (sh.global_batch,)
            leaves = jax.tree_util.tree_leaves(specs["cache"])
            assert leaves, "decode needs a cache"
            # windowed 'L' caches never exceed the window
            if cfg.sliding_window:
                import jax as _jax
                from repro.models import transformer as T
                cache = _jax.eval_shape(
                    lambda: T.init_cache(cfg, 1, sh.seq_len))
                k0 = cache["units"]["b0"]["k"]       # first block is 'L'
                assert k0.shape[2] == cfg.sliding_window

    def test_window_cache_invariance(self):
        """long_500k feasibility: gemma3 local layers cache O(window),
        only its 4 global layers carry the 524k sequence."""
        from repro.models import transformer as T
        cfg = get_config("gemma3-1b")
        cache = jax.eval_shape(lambda: T.init_cache(cfg, 1, 524_288))
        unit = cache["units"]
        local = unit["b0"]["k"].shape
        glob = unit["b5"]["k"].shape
        assert local[2] == 512
        assert glob[2] == 524_288


class TestRooflineMath:
    def test_terms_and_dominance(self):
        from benchmarks.bench_roofline import roofline_terms
        rec = {"n_devices": 256,
               "analytic": {"flops": 256 * 197e12, "hbm_bytes": 0.0,
                            "model_flops": 128 * 197e12},
               "collectives": {"total_bytes": 5e9},
               "cost_analysis": {}, "memory": {}}
        t = roofline_terms(rec)
        assert t["compute_s"] == pytest.approx(1.0)
        assert t["collective_s"] == pytest.approx(0.1)
        assert t["dominant"] == "compute"
        assert t["mfu_at_bound"] == pytest.approx(0.5)
