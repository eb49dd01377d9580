"""Validate the performance model against the paper's own numbers
(Tables 2/3/4, §2.2.3, §4.1 scenario theorems)."""
import dataclasses
import math

import pytest
from hypothesis import given, settings, strategies as st

from repro.core import perfmodel as pm
from repro.stencil import StencilSpec, alpha, fused_num_points

B21 = StencilSpec("box", 2, 1)
B23 = StencilSpec("box", 2, 3)
B27 = StencilSpec("box", 2, 7)
B31 = StencilSpec("box", 3, 1)
S21 = StencilSpec("star", 2, 1)


class TestAlpha:
    def test_paper_box2d1r_t3(self):
        # paper §2.2.3: fused 7x7 kernel = 49 ops vs 27 sequential
        assert fused_num_points(B21, 3) == 49
        assert alpha(B21, 3) == pytest.approx(49 / 27)
        assert alpha(B21, 3) == pytest.approx(1.81, abs=0.01)  # Table 2 row 5

    def test_paper_box2d1r_t7(self):
        assert alpha(B21, 7) == pytest.approx(3.57, abs=0.01)  # Table 2 rows 7/9

    def test_box_closed_form(self):
        # Eq. 10
        for d, r, t in [(2, 1, 3), (2, 3, 2), (3, 1, 3), (3, 2, 2)]:
            spec = StencilSpec("box", d, r)
            expect = (2 * r * t + 1) ** d / (t * (2 * r + 1) ** d)
            assert alpha(spec, t) == pytest.approx(expect)

    def test_star_fused_is_l1_ball(self):
        # unit-radius star kernels compose into L1 balls:
        # 2D radius t -> 2t^2 + 2t + 1 points (NOT a star -- this is why
        # alpha must be computed from the composed support, not a formula)
        for t in (2, 3, 4):
            assert fused_num_points(StencilSpec("star", 2, 1), t) \
                == 2 * t * t + 2 * t + 1
        # r=2 star sumset: box(r=2) plus axis spurs to distance 4 = 33
        assert fused_num_points(StencilSpec("star", 2, 2), 2) == 33

    def test_alpha_t1_is_1(self):
        for spec in (B21, B27, B31, S21):
            assert alpha(spec, 1) == 1.0


class TestTable2:
    """Analytical C and I columns of paper Table 2."""

    @pytest.mark.parametrize("spec,t,D,C,I", [
        (B21, 3, 8, 54, 3.38), (B23, 1, 8, 98, 6.12),
        (B21, 7, 4, 126, 15.75), (B27, 1, 4, 450, 56.25),
    ])
    def test_ebisu_rows(self, spec, t, D, C, I):
        w = pm.StencilWorkload(spec, t, D)
        assert w.flops_vector() == C
        assert w.intensity_vector() == pytest.approx(I, abs=0.01)

    @pytest.mark.parametrize("spec,t,D,S,C,I", [
        (B21, 3, 8, 0.5, 196, 12.25),      # ConvStencil
        (B21, 7, 4, 0.5, 900, 112.5),      # ConvStencil float
        (B21, 7, 4, 0.47, 960, 120.0),     # SPIDER (S=0.47 rounds C to 957)
    ])
    def test_tensor_core_rows(self, spec, t, D, S, C, I):
        w = pm.StencilWorkload(spec, t, D)
        assert w.flops_matrix(S) == pytest.approx(C, rel=0.01)
        assert w.intensity_matrix(S) == pytest.approx(I, rel=0.01)


class TestRidgePoints:
    def test_table3_ridges(self):
        assert pm.A100_DOUBLE.ridge_vector == pytest.approx(5, abs=0.1)
        assert pm.A100_DOUBLE.ridge_matrix == pytest.approx(10, abs=0.1)
        assert pm.A100_FLOAT.ridge_vector == pytest.approx(10, abs=0.1)
        assert pm.A100_FLOAT.ridge_matrix == pytest.approx(81, abs=1)
        assert pm.A100_FLOAT.ridge_sparse == pytest.approx(161, abs=1)


class TestScenarios:
    """Paper Table 3: six representative cases."""

    def test_case1_mb_to_cb_degrades(self):
        c = pm.compare(pm.StencilWorkload(B21, 3, 8), pm.A100_DOUBLE, 0.5)
        assert c.scenario is pm.Scenario.MB_CB
        assert c.speedup < 1.0                      # 27% degradation observed

    def test_case2_boundary(self):
        c = pm.compare(pm.StencilWorkload(B23, 1, 8), pm.A100_DOUBLE, 0.5)
        assert c.scenario is pm.Scenario.CB_CB
        assert c.speedup == pytest.approx(1.0, abs=0.01)   # ~equal perf

    def test_case3_case4_break_ceiling(self):
        for spec in (B21, B27):
            t = 7 if spec is B21 else 1
            c = pm.compare(pm.StencilWorkload(spec, t, 4), pm.A100_FLOAT,
                           0.47, use_sparse_unit=True)
            assert c.scenario is pm.Scenario.CB_MB
            assert c.speedup > 1.0

    def test_case5_case6_outside_sweet_spot(self):
        c5 = pm.compare(pm.StencilWorkload(B31, 3, 8), pm.A100_DOUBLE, 0.5)
        assert c5.scenario is pm.Scenario.CB_CB and c5.speedup < 1.0
        c6 = pm.compare(pm.StencilWorkload(B31, 7, 4), pm.A100_FLOAT, 0.47,
                        use_sparse_unit=True)
        assert c6.scenario is pm.Scenario.CB_CB and c6.speedup < 1.0

    def test_table4_sptc_bottleneck_flip(self):
        w = pm.StencilWorkload(B21, 7, 4)
        dense = pm.perf_matrix(w, pm.A100_FLOAT, 0.47)
        sparse = pm.perf_sparse_matrix(w, pm.A100_FLOAT, 0.47)
        assert dense.bound is pm.Bound.COMPUTE       # I=120 > ridge 81
        assert sparse.bound is pm.Bound.MEMORY       # I=120 < ridge 161
        # model predicts 1.49x from roofline terms alone; the paper's 3.06x
        # empirical gain includes the dense baseline underachieving its roof
        assert sparse.actual_flops / dense.actual_flops > 1.4


class TestScenarioTheorems:
    """Eq. 14/16/17: the scenario inequalities hold for ANY valid inputs."""

    @given(d=st.integers(1, 3), r=st.integers(1, 4), t=st.integers(1, 8),
           D=st.sampled_from([2, 4, 8]),
           S=st.floats(0.05, 1.0),
           shape=st.sampled_from(["box", "star"]))
    @settings(max_examples=200, deadline=None)
    def test_inequalities(self, d, r, t, D, S, shape):
        w = pm.StencilWorkload(StencilSpec(shape, d, r), t, D)
        c = pm.compare(w, pm.A100_FLOAT, S)
        if c.scenario is pm.Scenario.MB_MB:
            assert c.speedup == pytest.approx(1.0, rel=1e-6)   # Eq. 14
        elif c.scenario is pm.Scenario.MB_CB:
            assert c.speedup < 1.0 + 1e-9                      # Eq. 16
        elif c.scenario is pm.Scenario.CB_MB:
            assert c.speedup > 1.0 - 1e-9                      # Eq. 17
        else:
            # Eq. 18/19: profitable iff alpha < S * P_TC / P_CU
            lhs = w.alpha
            assert c.profitable == (lhs < c.sweet_spot_alpha_limit)

    @given(t=st.integers(1, 16))
    @settings(max_examples=50, deadline=None)
    def test_fusion_scales_intensity_linearly(self, t):
        w1 = pm.StencilWorkload(B21, 1, 4)
        wt = pm.StencilWorkload(B21, t, 4)
        assert wt.intensity_vector() == pytest.approx(t * w1.intensity_vector())


class TestSelector:
    def test_transition_depths(self):
        # paper §4.2 (A100 float): box transitions ~t=3, star ~t=5
        from repro.core.selector import transition_depth
        tb = transition_depth(B21, 4, pm.A100_FLOAT)
        ts = transition_depth(S21, 4, pm.A100_FLOAT)
        assert tb is not None and ts is not None
        assert tb <= 5 and ts >= tb   # star needs deeper fusion than box

    def test_selector_returns_valid_backend(self):
        from repro.core.selector import select_backend
        for t in (1, 3, 8):
            d = select_backend(B21, t, 4)
            expect = ("direct", "matmul") if t == 1 else \
                ("fused_direct", "fused_matmul", "fused_matmul_reuse")
            assert d.backend in expect
            assert d.reason

    def test_banded_sparsity_grows_with_radius(self):
        s1 = pm.sparsity_banded(1, 128)
        s8 = pm.sparsity_banded(8, 128)
        assert 0 < s1 < s8 < 1


class TestReuseRegime:
    """The intermediate-reuse MXU regime (DESIGN.md §4): alpha=1, priced by
    the halo-recompute factor beta instead."""

    def test_beta_formula(self):
        assert pm.halo_recompute_factor(1, 1) == 1.0
        assert pm.halo_recompute_factor(2, 4, strip_m=32) == \
            pytest.approx(1 + 2 * 3 / 32)
        # beta -> 1 as strips grow; monotone in t and r
        assert pm.halo_recompute_factor(1, 8, 1024) < \
            pm.halo_recompute_factor(1, 8, 32) < \
            pm.halo_recompute_factor(3, 8, 32)

    def test_beta_column_tiled_adds_x_axis(self):
        """On the column-tiled substrate (DESIGN.md §10) the carried
        x-halo is recomputed per step like the leading halos: the tile
        width joins the product mean, full-width betas are unchanged."""
        assert pm.reuse_beta(B21, 4, 32) == \
            pm.halo_recompute_factor(1, 4, 32)           # full width: 2D
        got = pm.reuse_beta(B21, 4, 32, w_tile=64)
        want = pm.halo_recompute_factor_nd(1, 4, (32, 64))
        assert got == pytest.approx(want)
        assert got > pm.reuse_beta(B21, 4, 32)           # strictly costlier
        # 3D: (z_slab, strip_m, w_tile) product mean
        spec3 = type(B21)("box", 3, 1)
        got3 = pm.reuse_beta(spec3, 2, 16, z_slab=8, w_tile=64)
        assert got3 == pytest.approx(
            pm.halo_recompute_factor_nd(1, 2, (8, 16, 64)))
        # lifted 1D never column-tiles and never recomputes
        spec1 = type(B21)("box", 1, 1)
        assert pm.reuse_beta(spec1, 4, 1) == 1.0

    def test_intensity_formula(self):
        # I_reuse = beta * t * K / (S * D)  (ISSUE: t*K/(S*D) as beta -> 1)
        w = pm.StencilWorkload(B21, 4, 4)
        S = pm.sparsity_banded(1, 128)
        beta = pm.halo_recompute_factor(1, 4, 128)
        assert w.intensity_matrix_reuse(S, 128) == \
            pytest.approx(beta * 4 * 9 / (S * 4))
        # no alpha anywhere: executed flops scale with beta, not alpha
        assert w.flops_matrix_reuse(S) < w.flops_matrix(S)

    def test_actual_deflates_by_s_over_beta(self):
        w = pm.StencilWorkload(B21, 7, 4)
        p = pm.perf_matrix_reuse(w, pm.A100_FLOAT, 0.47, strip_m=128)
        beta = pm.halo_recompute_factor(1, 7, 128)
        assert p.actual_flops == pytest.approx(0.47 / beta * p.raw_flops)
        assert p.unit == "matrix_reuse"

    def test_reuse_beats_monolithic_at_depth(self):
        """At SPIDER-like S the reuse regime dominates monolithic fusion
        (beta ~ 1.05 vs alpha ~ 3.57 at t=7) -- and the selector says so."""
        from repro.core.selector import select_backend
        w = pm.StencilWorkload(B21, 7, 4)
        mono = pm.perf_matrix(w, pm.A100_FLOAT, 0.47)
        reuse = pm.perf_matrix_reuse(w, pm.A100_FLOAT, 0.47)
        assert reuse.actual_flops > mono.actual_flops
        d = select_backend(B21, 7, 4, hw=pm.A100_FLOAT, sparsity=0.47)
        assert d.backend == "fused_matmul_reuse"
        assert "alpha=1" in d.reason
        assert d.predicted_speedup > 1.0

    def test_t1_reuse_degenerates_to_matmul(self):
        w = pm.StencilWorkload(B21, 1, 4)
        S = pm.sparsity_banded(1, 128)
        assert w.flops_matrix_reuse(S) == pytest.approx(w.flops_matrix(S))


class TestMatrixPeakByDtype:
    """The matrix unit is priced at the peak of the dtype it contracts:
    the v5e's MXU runs an f32 contraction at Precision.HIGHEST as six
    bf16 passes, so f32 sees 197/6 TFLOP/s; bf16 and the A100 specs
    (whose peaks are already their float peaks) price as before."""

    V5E = pm.TPU_V5E_BF16

    @staticmethod
    def _decide(spec, grid, t, dtype_bytes, hw=pm.TPU_V5E_BF16):
        from repro.kernels.common import resolve_substrate_geom
        from repro.kernels.plan import decide
        g = resolve_substrate_geom(grid, t * spec.radius, dtype_bytes)
        three = spec.dim == 3
        return decide(spec, t, dtype_bytes, hw, strip_m=g.strip_m,
                      h_block=g.h_block,
                      z_slab=g.z_slab if three else None,
                      z_block=g.z_block if three else None,
                      w_tile=g.w_tile, w_block=g.w_block, grid_shape=grid)

    def test_matrix_peak_per_dtype(self):
        assert self.V5E.f32_matrix_passes == 6
        assert pm.matrix_peak(self.V5E, 2) == 197e12
        assert pm.matrix_peak(self.V5E, 4) == 197e12 / 6
        assert pm.matrix_peak(pm.A100_FLOAT, 4) == 156e12
        assert pm.matrix_peak(pm.A100_DOUBLE, 8) == 19.5e12

    def test_star3d_f32_leaves_the_mxu_by_a_margin(self):
        """Star-3D1R f32 1024^3 t=2 at its resolved geometry: every unit
        was memory-bound at the bf16 peak and the MXU won a rounding tie;
        at six passes the VPU wins by more than 1.5x (2.1x at the read
        amplification 3 of the ring before grid steps were priced; the
        priced ring reads 4x, and the memory-bound VPU's price falls by
        4/5)."""
        d = self._decide(StencilSpec("star", 3, 1), (1024,) * 3, 2, 4)
        assert d.backend == "fused_direct"
        assert d.candidates["fused_matmul_reuse"] * 1.5 < \
            d.candidates["fused_direct"]
        assert d.scenario is pm.Scenario.MB_CB
        assert "strictly worse (Eq. 16)" in d.reason
        assert "read_amp=4.000x, grid_steps=65536 (" in d.reason

    @pytest.mark.parametrize("grid", [(10240, 10240), (10248, 10248),
                                      (256, 256)])
    def test_box2d_f32_picks_unchanged(self, grid):
        d = self._decide(B21, grid, 4, 4)
        assert d.backend == "fused_direct"

    @pytest.mark.parametrize("spec,grid,t", [
        (B21, (10240, 10240), 4), (B21, (256, 256), 1),
        (S21, (2048, 300), 2), (StencilSpec("star", 3, 1), (1024,) * 3, 2),
        (B31, (64, 64, 256), 3), (StencilSpec("box", 1, 2), (4096,), 4),
    ])
    def test_bf16_pricing_is_the_single_pass_pricing(self, spec, grid, t):
        """bf16 decisions are byte-identical to those of a spec whose f32
        contractions took one pass (the pricing before the pass count)."""
        one_pass = dataclasses.replace(self.V5E, f32_matrix_passes=1)
        new = self._decide(spec, grid, t, 2)
        old = self._decide(spec, grid, t, 2, hw=one_pass)
        assert new.candidates == old.candidates
        assert new.backend == old.backend and new.reason == old.reason
        w = pm.StencilWorkload(spec, t, 2)
        s = pm.sparsity_banded(spec.radius, 128)
        assert pm.perf_matrix(w, self.V5E, s) == \
            pm.perf_matrix(w, one_pass, s)
        assert pm.perf_matrix_reuse(w, self.V5E, s) == \
            pm.perf_matrix_reuse(w, one_pass, s)

    @pytest.mark.parametrize("hw,D", [(pm.A100_FLOAT, 4),
                                      (pm.A100_DOUBLE, 8)])
    @pytest.mark.parametrize("spec,t", [(B21, 1), (B21, 7), (B31, 3),
                                        (S21, 5)])
    def test_a100_prices_unchanged(self, hw, D, spec, t):
        w = pm.StencilWorkload(spec, t, D)
        s = 0.47
        for p, i in ((pm.perf_matrix(w, hw, s), w.intensity_matrix(s)),
                     (pm.perf_matrix_reuse(w, hw, s),
                      w.intensity_matrix_reuse(s))):
            assert p.raw_flops == min(hw.p_matrix, hw.bandwidth * i)
            assert p.ridge == hw.ridge_matrix
        c = pm.compare(w, hw, s)
        assert c.sweet_spot_alpha_limit == s * hw.p_matrix / hw.p_vector
