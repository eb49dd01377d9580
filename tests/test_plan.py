"""Plan API: compile-once plans, backend registry, cache keying/counters,
and the single-decision-path guarantee (``explain`` vs ``auto``)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perfmodel as pm
from repro.core import selector
from repro.kernels import (BACKENDS, clear_plan_cache, explain, get_backend,
                           plan_cache_stats, register_backend,
                           registered_backends, stencil_apply, stencil_plan,
                           unregister_backend)
from repro.kernels.ref import stencil_direct_ref
from repro.stencil import StencilSpec, jacobi_weights, make_weights

RNG = np.random.default_rng(0)


def _x(h, w, dtype=np.float32):
    return jnp.asarray(RNG.normal(size=(h, w)).astype(dtype))


def _hms(stats=None):
    """The cache-churn core of plan_cache_stats(): hits/misses/size only
    (the guard counters -- build/exec failures, fallbacks, negative hits --
    have their own tests in test_guard.py and stay zero on clean runs)."""
    s = plan_cache_stats() if stats is None else stats
    return {k: s[k] for k in ("hits", "misses", "size")}


#: Every registered backend at its auto ring, plus the five core regimes
#: with the ring pinned to whole neighbor strips (h_block = strip_m = 32).
CORE_BACKENDS = ("direct", "fused_direct", "matmul", "fused_matmul",
                 "fused_matmul_reuse")
BACKEND_CASES = ([pytest.param(b, {}, id=b) for b in BACKENDS if b != "auto"]
                 + [pytest.param(b, {"h_block": 32}, id=f"{b}-h_block=strip_m")
                    for b in CORE_BACKENDS])


class TestPlanExecution:
    @pytest.mark.parametrize("backend, pins", BACKEND_CASES)
    def test_every_registered_backend_executes(self, backend, pins):
        """plan(x) runs every registered regime + reference via the
        registry, and the core regimes on the whole-strip pin, and
        matches the oracle."""
        w = make_weights(StencilSpec("box", 2, 1), seed=1)
        x = _x(64, 64)
        t = 3
        plan = stencil_plan(w, x.shape, x.dtype, t, backend=backend,
                            tile_m=32, tile_n=32, **pins)
        ref = stencil_direct_ref(x, w, t)
        np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                                   atol=1e-4)

    @pytest.mark.parametrize("backend, pins", BACKEND_CASES)
    def test_wrapper_parity_bitwise(self, backend, pins):
        """stencil_apply == direct plan execution, bit-for-bit in f32."""
        w = make_weights(StencilSpec("star", 2, 2), seed=2)
        x = _x(64, 64)
        t = 2
        plan = stencil_plan(w, x.shape, x.dtype, t, backend=backend,
                            tile_m=32, tile_n=32, **pins)
        via_wrapper = stencil_apply(x, w, t=t, backend=backend,
                                    tile_m=32, tile_n=32, **pins)
        assert np.array_equal(np.asarray(plan(x)), np.asarray(via_wrapper))

    def test_step_and_run(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(32, 32)
        plan = stencil_plan(w, x.shape, x.dtype, 2, tile_m=16, tile_n=16)
        np.testing.assert_array_equal(np.asarray(plan.step(x)),
                                      np.asarray(plan(x)))
        two = plan(plan(x))
        np.testing.assert_array_equal(np.asarray(plan.run(x, 2)),
                                      np.asarray(two))
        np.testing.assert_array_equal(np.asarray(plan.run(x, 0)),
                                      np.asarray(x))

    def test_spec_input_uses_jacobi_weights(self):
        spec = StencilSpec("box", 2, 1)
        x = _x(32, 32)
        plan = stencil_plan(spec, x.shape, x.dtype, 1, tile_m=16, tile_n=16)
        ref = stencil_direct_ref(x, jacobi_weights(spec), 1)
        np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                                   atol=2e-5)

    def test_geometry_mismatch_raises(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        plan = stencil_plan(w, (32, 32), np.float32, 1, tile_m=16, tile_n=16)
        with pytest.raises(ValueError, match="grid"):
            plan(_x(64, 64))

    def test_bad_depth_and_missing_shard_spec(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        with pytest.raises(ValueError, match="fusion depth"):
            stencil_plan(w, (32, 32), np.float32, 0)
        with pytest.raises(ValueError, match="shard_spec"):
            stencil_plan(w, (32, 32), np.float32, 1, mesh=object())

    def test_explain_mentions_override(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        plan = stencil_plan(w, (32, 32), np.float32, 4, backend="reference",
                            tile_m=16, tile_n=16)
        assert plan.backend == "reference"
        assert plan.decision.backend != "reference"   # oracle is unpriced
        assert "override" in plan.explain()
        assert plan.build_time_s >= 0.0


class TestPlanCache:
    def test_hit_miss_counters_and_keying(self):
        """Distinct dtype/t/hw/backend/tiling signatures get distinct plans;
        identical signatures hit."""
        clear_plan_cache()
        w = make_weights(StencilSpec("box", 2, 1), seed=5)
        base = dict(tile_m=16, tile_n=16)

        p1 = stencil_plan(w, (32, 32), np.float32, 2, **base)
        assert _hms() == {"hits": 0, "misses": 1, "size": 1}

        assert stencil_plan(w, (32, 32), np.float32, 2, **base) is p1
        assert plan_cache_stats()["hits"] == 1

        variants = [
            stencil_plan(w, (32, 32), jnp.bfloat16, 2, **base),     # dtype
            stencil_plan(w, (32, 32), np.float32, 3, **base),       # t
            stencil_plan(w, (32, 32), np.float32, 2,                # hw
                         hw=pm.A100_FLOAT, **base),
            stencil_plan(w, (32, 32), np.float32, 2,                # override
                         backend="reference", **base),
            stencil_plan(w, (32, 32), np.float32, 2,                # tiling
                         tile_m=32, tile_n=16),
            stencil_plan(w, (64, 32), np.float32, 2, **base),       # grid
        ]
        assert len({id(p) for p in variants + [p1]}) == len(variants) + 1
        stats = plan_cache_stats()
        assert stats["misses"] == 1 + len(variants)
        assert stats["size"] == 1 + len(variants)

    def test_distinct_weights_do_not_collide(self):
        """Same spec, different tap values => different plans (the cache
        keys on the weight content digest, not just the inferred spec)."""
        clear_plan_cache()
        wa = make_weights(StencilSpec("box", 2, 1), seed=1)
        wb = make_weights(StencilSpec("box", 2, 1), seed=2)
        x = _x(32, 32)
        pa = stencil_plan(wa, x.shape, x.dtype, 1, tile_m=16, tile_n=16)
        pb = stencil_plan(wb, x.shape, x.dtype, 1, tile_m=16, tile_n=16)
        assert pa is not pb
        np.testing.assert_allclose(np.asarray(pa(x)),
                                   np.asarray(stencil_direct_ref(x, wa, 1)),
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(pb(x)),
                                   np.asarray(stencil_direct_ref(x, wb, 1)),
                                   atol=2e-5)

    def test_wrapper_reuses_plan_without_reselection(self):
        """Repeated stencil_apply with an identical signature: cache hits,
        and select_backend is NOT invoked again (the acceptance criterion)."""
        clear_plan_cache()
        w = make_weights(StencilSpec("box", 2, 2), seed=7)
        x = _x(32, 32)
        y1 = stencil_apply(x, w, t=2, backend="auto", tile_m=16, tile_n=16)
        after_first = selector.invocation_count()
        s1 = plan_cache_stats()
        for _ in range(3):
            y = stencil_apply(x, w, t=2, backend="auto", tile_m=16, tile_n=16)
            np.testing.assert_array_equal(np.asarray(y), np.asarray(y1))
        s2 = plan_cache_stats()
        assert selector.invocation_count() == after_first
        assert s2["hits"] == s1["hits"] + 3
        assert s2["misses"] == s1["misses"]

    def test_use_cache_false_bypasses(self):
        clear_plan_cache()
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        p1 = stencil_plan(w, (32, 32), np.float32, 1, tile_m=16, tile_n=16,
                          use_cache=False)
        p2 = stencil_plan(w, (32, 32), np.float32, 1, tile_m=16, tile_n=16,
                          use_cache=False)
        assert p1 is not p2
        assert plan_cache_stats()["size"] == 0

    def test_lru_eviction_env_bound_keeps_stats_consistent(self, monkeypatch):
        """REPRO_PLAN_CACHE_SIZE bounds the LRU; overflow evicts the
        least-recently-used plan, and the hit/miss counters stay consistent
        across eviction (an evicted signature re-misses; a surviving one
        still hits)."""
        from repro.kernels import plan_cache_max

        clear_plan_cache()
        monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "2")
        assert plan_cache_max() == 2
        w = make_weights(StencilSpec("box", 2, 1), seed=9)
        base = dict(tile_m=16, tile_n=16)

        p1 = stencil_plan(w, (32, 32), np.float32, 1, **base)
        p2 = stencil_plan(w, (32, 32), np.float32, 2, **base)
        assert _hms() == {"hits": 0, "misses": 2, "size": 2}

        stencil_plan(w, (32, 32), np.float32, 3, **base)   # evicts t=1
        assert _hms() == {"hits": 0, "misses": 3, "size": 2}

        # surviving signature: hit, no rebuild
        assert stencil_plan(w, (32, 32), np.float32, 2, **base) is p2
        assert _hms() == {"hits": 1, "misses": 3, "size": 2}

        # evicted signature: full re-miss (fresh plan object)
        p1b = stencil_plan(w, (32, 32), np.float32, 1, **base)
        assert p1b is not p1
        s = plan_cache_stats()
        assert _hms(s) == {"hits": 1, "misses": 4, "size": 2}
        assert s["size"] <= plan_cache_max()

        monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "zero")
        with pytest.raises(ValueError, match="integer"):
            plan_cache_max()
        # a malformed bound surfaces BEFORE the cache is touched: nothing
        # is inserted, so eviction can never be silently disabled
        size_before = plan_cache_stats()["size"]
        with pytest.raises(ValueError, match="integer"):
            stencil_plan(w, (32, 32), np.float32, 4, **base)
        assert plan_cache_stats()["size"] == size_before
        monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "0")
        with pytest.raises(ValueError, match=">= 1"):
            plan_cache_max()
        monkeypatch.delenv("REPRO_PLAN_CACHE_SIZE")
        from repro.kernels import plan as plan_mod
        assert plan_cache_max() == plan_mod.PLAN_CACHE_MAX
        clear_plan_cache()


class TestSingleDecisionPath:
    """ops.explain and the auto branch can never disagree: both ARE
    plan.decision (regression for the pre-plan duplicated logic)."""

    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("r", [1, 2])
    @pytest.mark.parametrize("t", [1, 2, 4, 8])
    def test_explain_equals_plan_decision(self, shape, r, t):
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        plan = stencil_plan(w, (128, 128), np.float32, t)
        d = explain(w, t, dtype_bytes=4, hw=plan.hw)
        assert d == plan.decision
        assert plan.backend == plan.decision.backend   # no override => same

    @pytest.mark.parametrize("grid", [(64, 64), (192, 160)])
    def test_explain_with_grid_matches_plan_on_that_grid(self, grid):
        """Plans price the geometry resolved for THEIR grid; explain agrees
        whenever it is told the grid (the parity contract off 128-row
        grids, where the pricing defaults no longer coincide)."""
        w = make_weights(StencilSpec("box", 2, 1), seed=3)
        for t in (1, 2, 4):
            plan = stencil_plan(w, grid, np.float32, t)
            d = explain(w, t, dtype_bytes=4, hw=plan.hw, grid_shape=grid)
            assert d == plan.decision

    def test_explain_with_grid_threads_pins(self):
        """Explicit tile_m/h_block pins resolve identically in explain and
        stencil_plan -- including the whole-strip pin h_block=strip_m."""
        w = make_weights(StencilSpec("box", 2, 1), seed=3)
        grid = (64, 64)
        for pins in ({"tile_m": 32, "h_block": 32}, {"tile_m": 16},
                     {"tile_m": 32, "h_block": 8}):
            plan = stencil_plan(w, grid, np.float32, 2, **pins)
            d = explain(w, 2, dtype_bytes=4, hw=plan.hw, grid_shape=grid,
                        **pins)
            assert d == plan.decision

    def test_decision_candidates_are_priced_registry_subset(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        d = explain(w, 4, 4)
        assert set(d.candidates) <= set(registered_backends())
        # unpriced backends never show up as candidates
        assert "reference" not in d.candidates


class TestPlan3D:
    """The N-D tentpole's plan-layer acceptance: 3D plans build and run
    every halo-plane regime for the paper's Box/Star-3D workloads, and the
    decision path stays single (explain == plan.decision on grids whose
    resolved geometry differs from the pricing defaults)."""

    def _x3(self, z, h, w):
        return jnp.asarray(
            RNG.normal(size=(z, h, w)).astype(np.float32))

    @pytest.mark.parametrize("name", ["Box-3D1R", "Star-3D1R"])
    def test_all_registered_regimes_run_3d(self, name):
        spec = StencilSpec.from_name(name)
        w = make_weights(spec, seed=1)
        x = self._x3(12, 24, 32)
        t = 2
        ref = stencil_direct_ref(x, w, t)
        for backend in registered_backends():
            plan = stencil_plan(w, x.shape, x.dtype, t, backend=backend)
            np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                                       atol=2e-4)

    def test_wrapper_parity_bitwise_3d(self):
        w = make_weights(StencilSpec("box", 3, 1), seed=2)
        x = self._x3(12, 24, 32)
        plan = stencil_plan(w, x.shape, x.dtype, 2, tile_m=12, z_slab=6)
        via_wrapper = stencil_apply(x, w, t=2, tile_m=12, z_slab=6)
        assert np.array_equal(np.asarray(plan(x)), np.asarray(via_wrapper))

    @pytest.mark.parametrize("grid", [(12, 24, 32), (10, 36, 40)])
    def test_explain_parity_on_non128_3d_grids(self, grid):
        """The satellite's parity contract: explain(grid_shape=...) equals
        plan.decision -- including the substrate read-amp factor and the
        resolved (z_slab, strip_m, h) geometry in the reason string -- on
        3D grids where no axis is 128-divisible."""
        w = make_weights(StencilSpec("box", 3, 1), seed=3)
        for t in (1, 2):
            plan = stencil_plan(w, grid, np.float32, t)
            d = explain(w, t, dtype_bytes=4, hw=plan.hw, grid_shape=grid)
            assert d == plan.decision
            assert "read_amp=" in d.reason
            assert "z_slab=" in d.reason and "strip_m=" in d.reason
        # pins thread identically, including the whole-slab pin
        for pins in ({"tile_m": 12, "h_block": 12, "z_slab": 6,
                      "z_block": 6}, {"tile_m": 12, "z_slab": 6},
                     {"tile_m": 12, "h_block": 2, "z_slab": 6,
                      "z_block": 2}):
            plan = stencil_plan(w, (12, 24, 32), np.float32, 2, **pins)
            d = explain(w, 2, dtype_bytes=4, hw=plan.hw,
                        grid_shape=(12, 24, 32), **pins)
            assert d == plan.decision

    def test_explain_geometry_note_2d(self):
        """2D reasons carry the substrate note too (the satellite asks for
        both ranks)."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        d = explain(w, 2, 4, grid_shape=(64, 64))
        assert "read_amp=" in d.reason and "strip_m=" in d.reason

    def test_plan_1d_lift(self):
        """1D grids route through the lifted 2D substrate instead of
        crashing -- every priced regime runs and matches the oracle."""
        w = make_weights(StencilSpec("box", 1, 2), seed=4)
        x = jnp.asarray(RNG.normal(size=(96,)).astype(np.float32))
        ref = stencil_direct_ref(x, w, 3)
        for backend in ("direct", "fused_direct", "matmul", "fused_matmul",
                        "fused_matmul_reuse", "reference"):
            plan = stencil_plan(w, x.shape, x.dtype, 3, backend=backend)
            np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                                       atol=2e-5)
        d = explain(w, 3, 4, grid_shape=x.shape)
        assert d == stencil_plan(w, x.shape, x.dtype, 3).decision
        assert "1D lifted" in d.reason

    def test_rank_mismatch_raises(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        with pytest.raises(ValueError, match="rank"):
            stencil_plan(w, (12, 24, 32), np.float32, 1)

    def test_hybrid_substrate_rejected_everywhere(self):
        """z_block=0 names no block ring: the selector must refuse to
        price it exactly like resolve_substrate_geom refuses to build it
        (single-decision-path contract, with or without a grid)."""
        w = make_weights(StencilSpec("box", 3, 1), seed=0)
        with pytest.raises(ValueError, match="whole-slab"):
            explain(w, 2, 4, h_block=4, z_block=0)
        with pytest.raises(ValueError, match="whole-slab"):
            explain(w, 2, 4, grid_shape=(12, 24, 32), tile_m=12,
                    z_slab=6, h_block=2, z_block=0)
        with pytest.raises(ValueError, match="whole-slab"):
            stencil_plan(w, (12, 24, 32), np.float32, 2, tile_m=12,
                         z_slab=6, h_block=2, z_block=0)


class TestPlanColumnTiled:
    """The column-tiled W substrate at the plan layer (DESIGN.md §10):
    explain == plan.decision parity on awkward widths (the satellite's W
    in {257, 300, 1000} sweep, 2D and 3D), w_tile in the reason string
    and the cache key, and the budget-driven auto escalation."""

    @pytest.mark.parametrize("wid", [257, 300, 1000])
    def test_explain_parity_awkward_widths_2d(self, wid):
        w = make_weights(StencilSpec("box", 2, 1), seed=3)
        grid = (48, wid)
        for t in (1, 2):
            plan = stencil_plan(w, grid, np.float32, t)
            d = explain(w, t, dtype_bytes=4, hw=plan.hw, grid_shape=grid)
            assert d == plan.decision
            assert "w_tile=" in d.reason
        # pins thread identically, including explicit column tiles
        for pins in ({"w_tile": 0}, {"tile_m": 24, "h_block": 12,
                                     "w_tile": 64},
                     {"tile_m": 24, "h_block": 12, "w_tile": 64,
                      "w_block": 8}):
            plan = stencil_plan(w, grid, np.float32, 2, **pins)
            d = explain(w, 2, dtype_bytes=4, hw=plan.hw, grid_shape=grid,
                        **pins)
            assert d == plan.decision

    @pytest.mark.parametrize("wid", [257, 300, 1000])
    def test_explain_parity_awkward_widths_3d(self, wid):
        w = make_weights(StencilSpec("box", 3, 1), seed=3)
        grid = (12, 24, wid)
        for t in (1, 2):
            plan = stencil_plan(w, grid, np.float32, t)
            d = explain(w, t, dtype_bytes=4, hw=plan.hw, grid_shape=grid)
            assert d == plan.decision
            assert "w_tile=" in d.reason
        pins = {"tile_m": 12, "z_slab": 6, "h_block": 2, "z_block": 2,
                "w_tile": 32}
        plan = stencil_plan(w, grid, np.float32, 2, **pins)
        d = explain(w, 2, dtype_bytes=4, hw=plan.hw, grid_shape=grid, **pins)
        assert d == plan.decision
        assert "w_tile=32" in d.reason

    def test_fullwidth_reason_reports_w_tile(self):
        """Every 2D/3D reason now reports the resolved width policy --
        'w_tile=full' on the fast path."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        d = explain(w, 2, 4, grid_shape=(64, 64))
        assert "w_tile=full" in d.reason

    def test_awkward_width_plans_execute(self):
        """Plans on awkward widths execute through the remainder path and
        match the oracle -- with an explicit column tile AND fully auto."""
        w = make_weights(StencilSpec("star", 2, 1), seed=2)
        x = _x(48, 257)
        ref = stencil_direct_ref(x, w, 2)
        for pins in ({}, {"tile_m": 24, "h_block": 12, "w_tile": 64}):
            plan = stencil_plan(w, x.shape, x.dtype, 2, **pins)
            np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                                       atol=1e-4)

    def test_cache_keys_on_w_tile_and_budget(self, monkeypatch):
        """Distinct w_tile pins get distinct plans; retuning
        REPRO_VMEM_BUDGET invalidates (the auto geometry depends on it)."""
        from repro.kernels import clear_plan_cache, plan_cache_stats

        clear_plan_cache()
        w = make_weights(StencilSpec("box", 2, 1), seed=5)
        base = dict(tile_m=24, h_block=12)
        p1 = stencil_plan(w, (48, 256), np.float32, 1, **base)
        p2 = stencil_plan(w, (48, 256), np.float32, 1, w_tile=64, **base)
        p3 = stencil_plan(w, (48, 256), np.float32, 1, w_tile=64,
                          w_block=16, **base)
        assert len({id(p) for p in (p1, p2, p3)}) == 3
        assert stencil_plan(w, (48, 256), np.float32, 1, w_tile=64,
                            **base) is p2
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "65536")
        p4 = stencil_plan(w, (48, 256), np.float32, 1, w_tile=64, **base)
        assert p4 is not p2                    # budget is part of the key
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "not-a-number")
        with pytest.raises(ValueError, match="integer"):
            stencil_plan(w, (48, 256), np.float32, 1, **base)
        monkeypatch.delenv("REPRO_VMEM_BUDGET")
        clear_plan_cache()

    def test_budget_driven_auto_column_tiles_through_plan(self, monkeypatch):
        """Under a tiny budget the fully-auto plan column-tiles: the
        decision reason reports a positive w_tile and execution matches
        the oracle bit-for-bit (box VPU path, t=1)."""
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "16384")
        w = make_weights(StencilSpec("box", 2, 1), seed=7)
        x = _x(32, 1024)
        plan = stencil_plan(w, x.shape, x.dtype, 1, backend="direct",
                            use_cache=False)
        assert "w_tile=" in plan.decision.reason
        assert "w_tile=full" not in plan.decision.reason
        ref = stencil_direct_ref(x, w, 1)
        np.testing.assert_array_equal(np.asarray(plan(x)), np.asarray(ref))


class TestRegistry:
    def test_unknown_backend_raises(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        with pytest.raises(ValueError, match="unknown backend"):
            stencil_apply(_x(32, 32), w, backend="gpu")
        with pytest.raises(ValueError):
            get_backend("gpu")

    def test_duplicate_and_auto_registration_rejected(self):
        with pytest.raises(ValueError, match="already registered"):
            register_backend("direct", lambda ctx: None)
        with pytest.raises(ValueError, match="auto"):
            register_backend("auto", lambda ctx: None)

    def test_custom_backend_is_additive(self):
        """A plug-in backend (e.g. a future sparse unit) becomes dispatchable
        through stencil_apply just by registering."""
        name = "test_scaled_reference"

        def build(ctx):
            from repro.kernels import ref
            w, t = ctx.weights, ctx.t

            def run(x):
                return ref.stencil_direct_ref(x, w, t)
            return run

        register_backend(name, build, description="test-only")
        try:
            assert name in registered_backends()
            # BACKENDS is computed on access: plug-ins show up immediately
            import repro.kernels as K
            assert name in K.BACKENDS
            w = make_weights(StencilSpec("box", 2, 1), seed=0)
            x = _x(32, 32)
            y = stencil_apply(x, w, t=2, backend=name)
            np.testing.assert_allclose(np.asarray(y),
                                       np.asarray(stencil_direct_ref(x, w, 2)),
                                       atol=2e-5)
            # unpriced: never appears in selection candidates
            assert name not in explain(w, 2, 4).candidates
        finally:
            unregister_backend(name)
        import repro.kernels as K
        assert name not in K.BACKENDS

    def test_priced_plugin_participates_in_selection(self):
        """Registering a priced backend makes the selector consider it --
        and invalidates previously cached 'auto' plans, so what executes
        can never disagree with what explain() reports."""
        name = "test_always_wins"
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(32, 32)
        stale = stencil_plan(w, x.shape, x.dtype, 3)     # cached pre-plugin

        def build(ctx):
            from repro.kernels import ref
            wts, t = ctx.weights, ctx.t
            return lambda x: ref.stencil_direct_ref(x, wts, t)

        register_backend(name, build, price=lambda p: float("inf"))
        try:
            d = explain(w, 3, 4)
            assert d.backend == name
            assert name in d.reason      # plug-ins get a plug-in reason
            plan = stencil_plan(w, x.shape, x.dtype, 3)  # NOT the stale plan
            assert plan is not stale
            assert plan.backend == name
            np.testing.assert_allclose(
                np.asarray(plan(x)),
                np.asarray(stencil_direct_ref(x, w, 3)), atol=2e-5)
        finally:
            unregister_backend(name)
        # after teardown a fresh build re-selects among the built-ins again
        plan = stencil_plan(w, x.shape, x.dtype, 3)
        assert plan.backend in registered_backends()
        assert plan.backend != name
