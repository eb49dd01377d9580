"""Static plan auditor (repro.audit, DESIGN.md §13): the block-access /
scratch / FLOP proofs pass on every Pallas backend across ranks and
remainder widths, the plan layer attaches reports and counts, the
explain reason-string read-amp has an audited third witness, and every
violation class is demonstrably CAUGHT by the negative harness (fault
injection, corrupted geometry, monkeypatched model)."""
import dataclasses
import math

import numpy as np
import pytest

from repro import audit
from repro.audit.blocks import audited_read_amp, enumerate_fetches
from repro.core import perfmodel as pm
from repro.kernels import clear_plan_cache, explain, plan_cache_stats, \
    stencil_plan
from repro.kernels import registry
from repro.kernels.common import launch_geometry, resolve_substrate_geom
from repro.stencil import StencilSpec, make_weights
from repro.testing import faults

CORE_BACKENDS = ("direct", "fused_direct", "matmul", "fused_matmul",
                 "fused_matmul_reuse")


@pytest.fixture(autouse=True)
def _hygiene():
    faults.reset_faults()
    clear_plan_cache()
    yield
    faults.reset_faults()
    clear_plan_cache()


def _ctx(grid, t=2, shape="box", r=1, **pins):
    spec = StencilSpec(shape, len(grid), r)
    w = make_weights(spec, seed=r)
    return registry.PlanContext(
        spec=spec, weights=w, grid_shape=tuple(grid),
        dtype=np.dtype(np.float32), t=t, tile_m=pins.get("tile_m"),
        tile_n=None, interpret=True, h_block=pins.get("h_block"),
        z_slab=pins.get("z_slab"), z_block=pins.get("z_block"),
        w_tile=pins.get("w_tile"), w_block=pins.get("w_block"))


# ---------------------------------------------------------------------------
# Positive sweep: audited structure == analytic model everywhere
# ---------------------------------------------------------------------------
class TestAuditSweep:
    """Satellite (c): audited bytes equal the analytic formula on awkward
    widths and off-128 3D grids, every Pallas backend, including the
    edge-tile remainder path."""

    @pytest.mark.parametrize("backend", CORE_BACKENDS)
    @pytest.mark.parametrize("wid", [257, 300, 1000])
    def test_remainder_width_2d(self, backend, wid):
        # Pinned w_tile forces the column-tiled walk; 257 and 300 take
        # the non-dividing edge-tile path, 1000 a dividing-but-odd one.
        ctx = _ctx((128, wid), t=2, w_tile=128 if wid != 1000 else 125,
                   w_block=32 if wid != 1000 else 25)
        rep = audit.audit_context(ctx, backend)
        assert rep.exempt is None
        assert rep.ok, rep.summary()
        byte_checks = [c for c in rep.checks
                       if c.name == "blocks/grid-bytes-model"
                       and not c.skipped]
        assert byte_checks, "byte-model check must run on these grids"
        for c in byte_checks:
            assert c.expected == c.actual

    @pytest.mark.parametrize("backend", CORE_BACKENDS)
    @pytest.mark.parametrize("grid", [(32, 64, 128), (24, 48, 100)])
    def test_3d_grids(self, backend, grid):
        rep = audit.audit_context(_ctx(grid, t=2), backend)
        assert rep.exempt is None
        assert rep.ok, rep.summary()

    @pytest.mark.parametrize("backend", CORE_BACKENDS)
    def test_wholestrip_foils(self, backend):
        """The ring pinned to whole neighbor strips (h_block = strip_m,
        read amp 3) audits clean like any other ring."""
        rep = audit.audit_context(
            _ctx((256, 512), t=2, tile_m=32, h_block=32), backend)
        assert rep.exempt is None
        assert rep.ok, rep.summary()

    @pytest.mark.parametrize("backend", CORE_BACKENDS)
    def test_1d_lift(self, backend):
        rep = audit.audit_context(_ctx((1000,), t=2), backend)
        assert rep.ok, rep.summary()

    def test_reference_exempt(self):
        rep = audit.audit_context(_ctx((128, 256)), "reference")
        assert rep.exempt is not None
        assert rep.ok and not rep.checks

    def test_fetch_enumeration_matches_formula_exactly(self):
        """The dedup'd walk is integer-exact against the closed form on a
        non-degenerate sub-blocked geometry."""
        ctx = _ctx((256, 512), t=2)
        launch = registry.get_backend("fused_direct").audit(ctx).launches[0]
        lg = launch.launch_geometry()
        fetches, n_steps = enumerate_fetches(lg)
        audited = fetches * math.prod(lg.in_block) * 4
        from repro.kernels.common import hbm_read_bytes_per_step
        g = launch.geom
        assert audited == hbm_read_bytes_per_step(
            (256, 512), g.strip_m, 4, h_block=g.h_block,
            w_tile=g.w_tile, w_block=g.w_block)
        assert n_steps == math.prod(lg.grid)


# ---------------------------------------------------------------------------
# Explain parity: the reason string's read-amp gets an audited witness
# ---------------------------------------------------------------------------
class TestReasonReadAmpParity:
    """Satellite (d): explain()'s reason-string read-amp, the plan's
    priced SubstrateGeom.read_amp, and the audited BlockSpec walk all
    agree -- three independent witnesses of one number."""

    @pytest.mark.parametrize("grid,t", [((256, 512), 2), ((192, 160), 4),
                                        ((32, 64, 128), 2), ((1000,), 2)])
    def test_three_witnesses(self, grid, t):
        spec = StencilSpec("box", len(grid), 1)
        w = make_weights(spec, seed=1)
        d = explain(w, t, dtype_bytes=4, grid_shape=grid)
        geom_px = resolve_substrate_geom(grid, t * spec.radius, 4,
                                         None, None, None, None, None, None)
        check = audit.audit_reason_read_amp(d.reason, grid, geom_px,
                                            t * spec.radius, 4)
        assert check.passed and not check.skipped, check.to_dict()
        lg = launch_geometry(grid, geom_px, t * spec.radius,
                             t * spec.radius if geom_px.w_tile else 0)
        assert math.isclose(audited_read_amp(lg, 4), geom_px.read_amp,
                            rel_tol=1e-9)

    def test_missing_read_amp_in_reason_is_a_violation(self):
        geom_px = resolve_substrate_geom((256, 512), 2, 4,
                                         None, None, None, None, None, None)
        check = audit.audit_reason_read_amp("no geometry here", (256, 512),
                                            geom_px, 2, 4)
        assert not check.passed

    def test_wrong_quoted_amp_is_a_violation(self):
        geom_px = resolve_substrate_geom((256, 512), 2, 4,
                                         None, None, None, None, None, None)
        check = audit.audit_reason_read_amp(
            "scenario x | substrate read_amp=2.999x (geom)", (256, 512),
            geom_px, 2, 4)
        assert not check.passed


# ---------------------------------------------------------------------------
# Plan attachment and counters
# ---------------------------------------------------------------------------
class TestPlanAttachment:
    def test_audit_true_attaches_clean_report_and_counts(self):
        before = plan_cache_stats()
        plan = stencil_plan(make_weights(StencilSpec("box", 2, 1), seed=0),
                            (256, 512), np.float32, 2, interpret=True,
                            audit=True)
        assert plan.audit_report is not None
        assert plan.audit_report.ok, plan.audit_report.summary()
        assert any(c.name == "blocks/reason-read-amp"
                   for c in plan.audit_report.checks)
        after = plan_cache_stats()
        assert after["audits_run"] == before["audits_run"] + 1
        assert after["audit_violations"] == before["audit_violations"]

    def test_default_is_off_and_env_flag_turns_on(self, monkeypatch):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        plan = stencil_plan(w, (128, 256), np.float32, 1, interpret=True,
                            use_cache=False)
        assert plan.audit_report is None
        monkeypatch.setenv("REPRO_AUDIT", "1")
        plan = stencil_plan(w, (128, 256), np.float32, 1, interpret=True,
                            use_cache=False)
        assert plan.audit_report is not None

    def test_batched_plan_is_exempt_not_violating(self):
        plan = stencil_plan(make_weights(StencilSpec("box", 2, 1), seed=0),
                            (128, 256), np.float32, 1, interpret=True,
                            audit=True, batch=2, use_cache=False)
        assert plan.audit_report.exempt is not None
        assert plan.audit_report.ok

    def test_violations_count_but_never_fail_the_build(self):
        before = plan_cache_stats()["audit_violations"]
        with faults.inject("geometry", times=math.inf):
            plan = stencil_plan(
                make_weights(StencilSpec("box", 2, 1), seed=0),
                (256, 512), np.float32, 2, backend="fused_direct",
                interpret=True, audit=True, use_cache=False)
        assert plan.audit_report is not None
        assert not plan.audit_report.ok
        assert plan_cache_stats()["audit_violations"] > before


# ---------------------------------------------------------------------------
# Negative tests: every violation class is caught
# ---------------------------------------------------------------------------
class TestViolationClassesCaught:
    def test_geometry_fault_breaks_read_model(self):
        """Class 1 (read-model mismatch): the PR-6 'geometry' fault warps
        the block walk; the auditor must flag bytes AND coverage."""
        with faults.inject("geometry", times=math.inf):
            rep = audit.audit_context(_ctx((256, 512), t=2), "fused_direct",
                                      flops=False)
        names = {c.name for c in rep.violations}
        assert "blocks/grid-bytes-model" in names
        assert "scratch/coverage-global" in names

    def test_corrupted_index_map_via_monkeypatch(self):
        """Same class, without the fault harness: a hand-warped index map
        (the kind of off-by-one PR 5 fixed) is caught."""
        launch = registry.get_backend("fused_direct").audit(
            _ctx((256, 512), t=2)).launches[0]
        lg = launch.launch_geometry()
        orig = lg.in_index_map
        warped = lambda *ix: tuple(b + (1 if k == 0 else 0)
                                   for k, b in enumerate(orig(*ix)))
        bad = dataclasses.replace(lg, in_index_map=warped)
        checks = audit.audit_blocks(bad, launch, 4) \
            + audit.audit_scratch(bad, launch)
        assert any(not c.passed and not c.skipped for c in checks)

    def test_shrunken_read_window_is_a_coverage_hole(self):
        """Class 2 (scratch coverage hole): a read window short of the
        halo -- the silent-wrong-answer class -- is caught."""
        launch = registry.get_backend("fused_direct").audit(
            _ctx((256, 512), t=2)).launches[0]
        lg = launch.launch_geometry()
        (lo, hi), rest = lg.read_bounds[0], lg.read_bounds[1:]
        bad = dataclasses.replace(lg, read_bounds=((lo + 1, hi - 1),) + rest)
        checks = audit.audit_scratch(bad, launch)
        viol = [c for c in checks if not c.passed and not c.skipped]
        assert any(c.name == "scratch/read-window" for c in viol)

    def test_overlapping_slots_are_conflicting_writes(self):
        launch = registry.get_backend("fused_direct").audit(
            _ctx((256, 512), t=2)).launches[0]
        lg = launch.launch_geometry()
        bad = dataclasses.replace(
            lg, scratch_shape=(lg.scratch_shape[0] - lg.block_dims[0],)
            + lg.scratch_shape[1:])
        checks = audit.audit_scratch(bad, launch)
        viol = {c.name for c in checks if not c.passed and not c.skipped}
        assert "scratch/slots-partition" in viol

    def test_monkeypatched_reuse_beta_breaks_flop_model(self, monkeypatch):
        """Class 3 (FLOP/redundancy mismatch): a wrong beta in the model
        is caught by the jaxpr-counted ground truth."""
        orig = pm.reuse_beta
        monkeypatch.setattr(pm, "reuse_beta",
                            lambda *a, **k: orig(*a, **k) * 1.5)
        rep = audit.audit_context(_ctx((256, 512), t=2),
                                  "fused_matmul_reuse")
        names = {c.name for c in rep.violations}
        assert "flops/beta" in names

    def test_clean_run_has_zero_violations_and_exact_flops(self):
        """Control for the negatives: the same audits pass clean, with the
        structural FLOP check integer-exact."""
        for backend in ("fused_direct", "fused_matmul_reuse"):
            rep = audit.audit_context(_ctx((256, 512), t=2), backend)
            assert rep.ok, rep.summary()
            (c,) = [c for c in rep.checks if c.name == "flops/structural"]
            assert c.expected == c.actual
