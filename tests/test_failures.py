"""Failure-aware S-SGD: K-of-N partial sync and fault injection.

Pins the failure-model contracts:

* The ``fail:`` grammar round-trips, rejects malformed specs, and the
  seed-keyed crash matrices are deterministic and backend-independent.
* :func:`repro.core.analytical.kth_order_statistic` is the K-th
  smallest over the live (unpadded) workers — exact against ``np.sort``
  on random tables, in NumPy and **inside jit** via ``jax.lax.top_k``.
* ``sync_k = N`` (and 0/None/over-large K) is **bit-identical** to the
  historical full-sync path; iteration time is monotone non-increasing
  in K; ``K = 1`` waits only for the fastest worker.
* The K-of-N / fault closed forms agree with the event-driven DAG
  oracle to <= 1e-6 on the built-in grid and random grids, and the two
  batched backends agree draw-for-draw with faults enabled.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import pytest
from hypothesis import given, settings

from strategies import fault_specs, scenario_grids, sync_ks, worker_rates
from repro.core import analytical
from repro.core import het
from repro.core.scenarios import (Scenario, ScenarioGrid, default_grid,
                                  normalize_sync_k, validate_sync_k)
from repro.core.sweep import evaluate_scenario, sweep


class TestFaultGrammar:
    def test_parse_full_spec(self):
        ft = het.parse_fault("fail:0.05@restart2.5x500")
        assert ft == het.FaultSpec(p=0.05, restart=2.5, draws=500)
        assert not ft.is_deterministic

    def test_parse_defaults(self):
        ft = het.parse_fault("fail:0.1")
        assert ft.restart == het.DEFAULT_RESTART_S
        assert ft.draws == het.DEFAULT_DRAWS
        assert het.parse_fault("fail:0.1x64").draws == 64

    def test_none_and_normalize(self):
        assert het.parse_fault(None) is None
        assert het.parse_fault("none") is None
        assert het.normalize_fault(None) == "none"
        assert het.normalize_fault("fail:0.1") == "fail:0.1"

    def test_deterministic_degenerates(self):
        assert het.parse_fault("fail:0").is_deterministic
        assert het.parse_fault("fail:0.5@restart0").is_deterministic

    @pytest.mark.parametrize("bad", [
        "fail:", "fail:x", "fail:1.5", "fail:-0.1", "fail:0.1@boom2",
        "fail:0.1@restart-1", "fail:0.1@restartx", "fail:0.1x0",
        "fail:0.1x999999999", "lognormal:0.2", "0.1"])
    def test_malformed_specs_rejected(self, bad):
        with pytest.raises(ValueError):
            het.parse_fault(bad)

    def test_crash_matrix_seeded_and_shaped(self):
        ft = het.parse_fault("fail:0.3@restart1x200")
        a = ft.crash_matrix(8, seed=7)
        assert a.shape == (200, 8) and a.dtype == bool
        assert np.array_equal(a, ft.crash_matrix(8, seed=7))
        assert not np.array_equal(a, ft.crash_matrix(8, seed=8))
        # draw-count override re-keys the stream (shard/backend safety)
        assert ft.crash_matrix(8, seed=7, draws=64).shape == (64, 8)

    def test_crash_rate_matches_p(self):
        ft = het.parse_fault("fail:0.25@restart1x4000")
        rate = ft.crash_matrix(16, seed=0).mean()
        assert rate == pytest.approx(0.25, abs=0.02)

    def test_restart_penalty_from_checkpoint_size(self):
        # a 10 GB checkpoint over a 2 GB/s store reads in 5 s
        assert het.restart_penalty_s(10e9) == pytest.approx(5.0)
        with pytest.raises(ValueError):
            het.restart_penalty_s(-1.0)


class TestSyncKAxis:
    def test_normalize(self):
        assert normalize_sync_k(None) == 0
        assert normalize_sync_k("none") == 0
        assert normalize_sync_k(0) == 0
        assert normalize_sync_k(3) == 3

    def test_validate(self):
        validate_sync_k(None)
        validate_sync_k(4)
        with pytest.raises(ValueError):
            validate_sync_k(-1)
        with pytest.raises(ValueError):
            validate_sync_k("three")

    def test_scenario_label_and_grid_roundtrip(self):
        g = dataclasses.replace(
            default_grid(), workloads=("alexnet",), worker_counts=(8,),
            policies=("tensorflow",), sync_ks=(None, 6),
            faults=(None, "fail:0.01@restart2x8"))
        assert len(g) == len(g.expand())
        for i, s in enumerate(g.expand()):
            assert g.scenario_at(i) == s
            s.validate()
        labels = {s.label() for s in g.expand()}
        assert any("/k6" in l for l in labels)
        assert any("fail:0.01@restart2x8" in l for l in labels)

    def test_bad_axis_values_rejected(self):
        with pytest.raises(ValueError):
            dataclasses.replace(default_grid(),
                                sync_ks=(-2,)).validate_axes()
        with pytest.raises(ValueError):
            dataclasses.replace(default_grid(),
                                faults=("fail:2",)).validate_axes()


class TestKthOrderStatistic:
    @settings(max_examples=30, deadline=None)
    @given(worker_rates(), sync_ks())
    def test_matches_sort_on_random_vectors(self, rates, k):
        n = len(rates)
        keff = int(analytical.effective_sync_k(
            normalize_sync_k(k), n))
        got = analytical.kth_order_statistic(
            rates[None, :], np.array(n), np.array(keff))
        assert got[0] == np.sort(rates)[keff - 1]

    def test_padded_rows_ignore_pads(self):
        # zero-padded worker table rows: pads must never win
        vals = np.array([[3.0, 1.0, 2.0, 0.0, 0.0],
                         [5.0, 4.0, 0.0, 0.0, 0.0]])
        n = np.array([3, 2])
        k = np.array([2, 1])
        got = analytical.kth_order_statistic(vals, n, k)
        assert got.tolist() == [2.0, 4.0]

    def test_jitted_jax_top_k_agrees_with_numpy(self):
        import jax
        import jax.numpy as jnp

        rng = np.random.default_rng(0)
        vals = rng.uniform(0.1, 2.0, size=(32, 7))
        n = rng.integers(1, 8, size=32)
        vals *= np.arange(7) < n[:, None]          # zero-pad dead slots
        k = np.minimum(rng.integers(1, 8, size=32), n)
        want = analytical.kth_order_statistic(vals, n, k)
        with jax.enable_x64(True):
            got = jax.jit(analytical.kth_order_statistic)(
                jnp.asarray(vals), jnp.asarray(n), jnp.asarray(k))
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-12)

    def test_effective_sync_k_clamps(self):
        n = np.array([4, 4, 4, 4])
        k = np.array([0, 1, 4, 99])
        assert analytical.effective_sync_k(k, n).tolist() == [4, 1, 4, 4]

    def test_worker_bottleneck_k_full_sync_is_max(self):
        inv = np.array([[1.0, 2.0, 0.5]])
        bw = np.array([[1.0, 0.5, 1.0]])
        lat = np.array([[1.0, 2.0, 1.0]])
        t, b, l = analytical.worker_bottleneck_k(
            inv, bw, lat, np.array([3]), np.array([0]))
        t0, b0, l0 = analytical.worker_bottleneck(inv, bw, lat)
        assert (t[0], b[0], l[0]) == (t0[0], b0[0], l0[0]) == (2.0, 0.5, 2.0)
        # K=2 takes the 2nd smallest compute multiplier, links unchanged
        t2, b2, l2 = analytical.worker_bottleneck_k(
            inv, bw, lat, np.array([3]), np.array([2]))
        assert (t2[0], b2[0], l2[0]) == (1.0, 0.5, 2.0)


def _grid(**axes) -> ScenarioGrid:
    base = dict(workloads=("alexnet",), clusters=("v100-nvlink-ib",),
                worker_counts=(8,), policies=("tensorflow",),
                collectives=("ring",))
    base.update(axes)
    return ScenarioGrid(**base)


class TestKofNSemantics:
    def test_k_equals_n_bit_identical_to_full_sync(self):
        axes = dict(worker_counts=(8,),
                    policies=("tensorflow", "caffe-mpi", "bucketed-4mb"),
                    het_profiles=(None, "het:1x0.5+3x1.0"),
                    stragglers=(None, "lognormal:0.25x64"),
                    faults=(None, "fail:0.1@restart1x64"))
        full = sweep(_grid(sync_ks=(None,), **axes), seed=5)
        k_n = sweep(_grid(sync_ks=(8,), **axes), seed=5)
        over = sweep(_grid(sync_ks=(99,), **axes), seed=5)
        for c in ("iteration_time_s", "t_mean_s", "t_p95_s", "t_p99_s",
                  "samples_per_sec", "speedup"):
            assert np.array_equal(full.columns[c], k_n.columns[c]), c
            assert np.array_equal(full.columns[c], over.columns[c]), c

    def test_monotone_non_increasing_in_k(self):
        g = _grid(het_profiles=("het:2x0.4+2x0.8+4x1.2",),
                  sync_ks=tuple(range(1, 9)))
        t = sweep(g).columns["iteration_time_s"]
        assert np.all(np.diff(t) >= -1e-12)
        assert t[0] < t[-1]          # the het spread makes K matter

    def test_k1_waits_for_fastest_worker_only(self):
        prof = "het:1x0.5+7x1.0"     # one half-speed worker in 8
        inv, _, _ = het.worker_vectors(het.parse_het_profile(prof), 8)
        r1 = sweep(_grid(het_profiles=(prof,), sync_ks=(1,))).rows[0]
        # fastest worker: multiplier min(inv) — evaluate the equivalent
        # homogeneous scenario scaled to it via a uniform profile
        fast = sweep(_grid(het_profiles=(f"het:8x{1 / inv.min():g}",),
                           sync_ks=(None,))).rows[0]
        assert r1["iteration_time_s"] == pytest.approx(
            fast["iteration_time_s"], rel=1e-12)

    def test_homogeneous_sync_k_is_noop(self):
        full = sweep(_grid(sync_ks=(None,)))
        k3 = sweep(_grid(sync_ks=(3,)))
        assert np.array_equal(full.columns["iteration_time_s"],
                              k3.columns["iteration_time_s"])

    def test_fault_tails_shift_with_restart(self):
        base = sweep(_grid(faults=("fail:0.2@restart1x400",)), seed=1)
        dbl = sweep(_grid(faults=("fail:0.2@restart2x400",)), seed=1)
        r0, r1 = base.rows[0], dbl.rows[0]
        assert r1["t_mean_s"] > r0["t_mean_s"] > r0["iteration_time_s"]
        assert r1["iteration_time_s"] == r0["iteration_time_s"]

    def test_deterministic_fault_specs_keep_point_mass(self):
        for spec in ("fail:0@restart5x64", "fail:0.5@restart0x64"):
            r = sweep(_grid(faults=(spec,))).rows[0]
            assert r["t_mean_s"] == r["t_p99_s"] == r["iteration_time_s"]


class TestOracleAgreement:
    """Closed form vs the event-driven DAG simulator, <= 1e-6."""

    COLS = ("iteration_time_s", "t_mean_s", "t_p95_s", "t_p99_s")

    def assert_sim_agrees(self, grid, seed=0, rel=1e-6):
        fast = sweep(grid, seed=seed)
        sim = sweep(grid, force_simulator=True, seed=seed)
        for c in self.COLS:
            np.testing.assert_allclose(
                fast.columns[c], sim.columns[c], rtol=rel, err_msg=c)

    def test_builtin_grid_with_failure_axes(self):
        g = dataclasses.replace(
            default_grid(), workloads=("alexnet",),
            worker_counts=(4, 16), collectives=("ring",),
            interconnects=(None,),
            het_profiles=(None, "het:1x0.5+3x1.0"),
            stragglers=(None, "lognormal:0.25x16"),
            sync_ks=(None, 3), faults=(None, "fail:0.2@restart1.5x16"))
        self.assert_sim_agrees(g, seed=11)

    @settings(max_examples=5, deadline=None)
    @given(scenario_grids(with_het=True, with_failures=True))
    def test_random_grids_numpy_vs_simulator(self, grid):
        # keep the oracle affordable: simulator-eligible closed forms,
        # one workload/cluster slice of the drawn grid
        grid = dataclasses.replace(
            grid, workloads=grid.workloads[:1], clusters=grid.clusters[:1],
            policies=("tensorflow", "caffe-mpi"),
            worker_counts=grid.worker_counts[:2],
            interconnects=grid.interconnects[:1],
            stragglers=tuple(s for s in grid.stragglers
                             if s is None or "x8" in s or "x16" in s)
            or (None,),
            faults=tuple(f for f in grid.faults
                         if f is None or "x8" in f or "x16" in f)
            or (None,))
        self.assert_sim_agrees(grid, seed=3)

    @settings(max_examples=8, deadline=None)
    @given(scenario_grids(with_het=True, with_failures=True))
    def test_random_grids_numpy_vs_jax_draw_for_draw(self, grid):
        r = sweep(grid, seed=9)
        rj = sweep(grid, backend="jax", seed=9)
        for c in self.COLS + ("samples_per_sec", "speedup"):
            np.testing.assert_allclose(
                r.columns[c], rj.columns[c], rtol=1e-6, err_msg=c)
        for c in ("sync_k", "faults"):
            assert np.array_equal(r.columns[c], rj.columns[c]), c

    def test_single_scenario_oracle_with_crashes(self):
        s = Scenario("alexnet", "v100-nvlink-ib", 8, "tensorflow",
                     het="het:1x0.5+7x1.0", sync_k=6,
                     faults="fail:0.3@restart2x32")
        fast = evaluate_scenario(s, seed=2)
        sim = evaluate_scenario(s, method="simulator", seed=2)
        for c in self.COLS:
            assert fast[c] == pytest.approx(sim[c], rel=1e-6), c


class TestFailureColumnsAndCli:
    def test_result_filter_normalizes_failure_axes(self):
        g = _grid(sync_ks=(None, 4), faults=(None, "fail:0.1x8"))
        r = sweep(g)
        assert r.filter(sync_k=None) == r.filter(sync_k=0)
        assert r.filter(faults=None) == r.filter(faults="none")
        assert len(r.filter(sync_k=4, faults="fail:0.1x8")) == 1

    def test_format_table_shows_failure_columns(self):
        g = _grid(sync_ks=(4,), faults=("fail:0.1@restart1x8",))
        text = sweep(g).format_table()
        assert "faults" in text and "fail:0.1@restart1x8" in text

    def test_cli_flags(self, capsys, tmp_path):
        import json

        from repro.launch.sweep import main

        path = tmp_path / "cli.json"
        assert main(["--workloads", "alexnet", "--workers", "8",
                     "--policies", "tensorflow", "--sync-k", "none,6",
                     "--faults", "none,fail:0.05@restart1x8",
                     "--json", str(path)]) == 0
        out = capsys.readouterr().out
        assert "2 sync-k" in out and "2 faults" in out
        rows = json.loads(path.read_text())["rows"]
        assert {r["sync_k"] for r in rows} == {0, 6}
        assert {r["faults"] for r in rows} == {"none",
                                               "fail:0.05@restart1x8"}

    def test_cli_rejects_bad_fault_spec(self, capsys):
        from repro.launch.sweep import main

        assert main(["--faults", "fail:2"]) == 2
        assert "error" in capsys.readouterr().err
