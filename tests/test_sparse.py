"""Sparse band-compaction backends (DESIGN.md §14): the compacted MXU
contraction is bitwise-equal to the dense banded path, the closed-form
sparsity/kept-row formulas match the materialized operands, the static
auditor proves (and catches tampering of) the compaction metadata, and
the selector's sparse sweet spot agrees between ``ops.explain`` and the
built plan."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro import audit
from repro.core import perfmodel as pm
from repro.kernels import (band_sparsity, build_bands, build_bands_nd,
                           clear_plan_cache, explain, stencil_plan)
from repro.kernels import registry
from repro.kernels.plan import plan_signature
from repro.kernels.ref import stencil_direct_ref
from repro.kernels.stencil_sparse import (band_row_meta, compact_bands,
                                          kept_row_fraction)
from repro.stencil import StencilSpec, make_weights

RNG = np.random.default_rng(7)


@pytest.fixture(autouse=True)
def _fresh_cache():
    clear_plan_cache()
    yield
    clear_plan_cache()


def _weights(shape, r, seed=0):
    return make_weights(StencilSpec(shape, 2, r), seed=seed)


def _ctx(grid, t=2, shape="star", r=1, tile_n=None):
    spec = StencilSpec(shape, len(grid), r)
    w = make_weights(spec, seed=r)
    return registry.PlanContext(
        spec=spec, weights=w, grid_shape=tuple(grid),
        dtype=np.dtype(np.float32), t=t, tile_m=None, tile_n=tile_n,
        interpret=True, h_block=None, z_slab=None, z_block=None,
        w_tile=None, w_block=None)


# ---------------------------------------------------------------------------
# Satellites 1+2: closed-form band_sparsity and vectorized build_bands
# cross-checked against materialized/reference constructions
# ---------------------------------------------------------------------------
class TestBandConstruction:
    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("tile_n", [32, 128])
    def test_closed_form_sparsity_vs_materialized(self, shape, r, tile_n):
        """band_sparsity's closed form == nonzeros of the built operand."""
        w = np.asarray(_weights(shape, r), dtype=np.float32)
        _, bands = build_bands_nd(w, tile_n)
        measured = np.count_nonzero(bands) / bands.size
        assert band_sparsity(w, tile_n) == pytest.approx(measured, rel=1e-12)

    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("r", [1, 2, 3])
    def test_vectorized_build_matches_reference_loop(self, shape, r):
        """The vectorized diagonal fill == the naive triple loop."""
        w = np.asarray(_weights(shape, r), dtype=np.float32)
        tile_n = 64
        rows, kx = w.shape
        ref = np.zeros((rows, tile_n + 2 * r, tile_n), dtype=w.dtype)
        for row in range(rows):
            for dx in range(kx):
                for j in range(tile_n):
                    ref[row, j + dx, j] = w[row, dx]
        np.testing.assert_array_equal(build_bands(w, tile_n), ref)

    @pytest.mark.parametrize("shape,r", [("box", 1), ("box", 2),
                                         ("star", 1), ("star", 2),
                                         ("star", 3)])
    def test_compaction_hull(self, shape, r):
        """compact_bands keeps exactly the contiguous nonzero hull: the
        packed rows scatter back to the dense bands, the packed row count
        matches the kept_row_fraction closed form, and box kernels (every
        band row populated) compact to S = 1."""
        w = np.asarray(_weights(shape, r), dtype=np.float32)
        tile_n = 32
        offsets, bands = build_bands_nd(w, tile_n)
        row_index, packed = compact_bands(offsets, bands)
        assert len(row_index) == len(offsets)
        rebuilt = np.zeros_like(bands)
        start = 0
        for p, ix in enumerate(row_index):
            rebuilt[p, ix] = packed[start:start + ix.size]
            start += ix.size
        np.testing.assert_array_equal(rebuilt, bands)
        assert start == packed.shape[0] == sum(ix.size for ix in row_index)
        S = packed.shape[0] / (len(offsets) * (tile_n + 2 * r))
        assert kept_row_fraction(w, tile_n) == pytest.approx(S, rel=1e-12)
        if shape == "box":
            assert S == 1.0
        else:
            assert S < 1.0

    def test_row_meta_spans(self):
        w = np.asarray(_weights("star", 2), dtype=np.float32)
        offsets, bands = build_bands_nd(w, 32)
        row_index, packed = compact_bands(offsets, bands)
        meta = band_row_meta(row_index, 32)
        assert len(meta) == len(offsets)
        starts = [row_start for _, _, row_start in meta]
        assert starts == sorted(starts) and starts[0] == 0
        for lo, span, row_start in meta:
            assert 0 <= lo and 0 <= span and lo + span <= 4
        assert meta[-1][2] + 32 + meta[-1][1] == packed.shape[0]


# ---------------------------------------------------------------------------
# Satellite 3: bitwise equivalence of the compacted contraction
# ---------------------------------------------------------------------------
def _plans(w, grid, dtype, t, **pins):
    """(sparse, dense) plan pair at matched geometry for fusion depth t."""
    sp, dn = (("sparse_matmul", "matmul") if t == 1
              else ("fused_sparse_matmul", "fused_matmul_reuse"))
    mk = lambda b: stencil_plan(np.asarray(w), grid, dtype, t, backend=b,
                                interpret=True, **pins)
    return mk(sp), mk(dn)


class TestBitwiseEquivalence:
    """The compaction contract: dropping structurally-zero band rows is
    graph-equivalent, so sparse output == dense matmul output BITWISE
    (not merely close) on every shape/radius/depth/dtype/width."""

    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("t", [1, 2, 4])
    def test_shape_radius_depth(self, shape, r, t):
        w = _weights(shape, r, seed=r)
        x = jnp.asarray(RNG.normal(size=(32, 257)).astype(np.float32))
        sp, dn = _plans(w, x.shape, np.float32, t, tile_m=16)
        ys, yd = np.asarray(sp(x)), np.asarray(dn(x))
        assert np.array_equal(ys, yd), \
            f"sparse != dense bitwise: {shape} r={r} t={t}"
        ref = np.asarray(stencil_direct_ref(x, jnp.asarray(w), t))
        np.testing.assert_allclose(ys, ref, atol=1e-3, rtol=1e-4)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("wid", [257, 300])
    def test_dtype_and_remainder_width(self, dtype, wid):
        """Remainder chunks (257 -> 1-wide, 300 -> 44-wide tails at
        tile_n=128) re-expand to the dense prefix, keeping bitwise parity
        in both dtypes."""
        w = _weights("star", 2, seed=2)
        x = jnp.asarray(RNG.normal(size=(32, wid))).astype(dtype)
        sp, dn = _plans(w, x.shape, x.dtype.type, 2, tile_m=16)
        assert np.array_equal(np.asarray(sp(x)), np.asarray(dn(x))), \
            f"sparse != dense bitwise: {dtype} W={wid}"


# ---------------------------------------------------------------------------
# Audit: the compaction proofs pass -- and catch mis-compaction
# ---------------------------------------------------------------------------
class TestSparseAudit:
    @pytest.mark.parametrize("backend", ["sparse_matmul",
                                         "fused_sparse_matmul"])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_zero_violations(self, backend, shape):
        t = 1 if backend == "sparse_matmul" else 2
        rep = audit.audit_context(_ctx((256, 512), t=t, shape=shape),
                                  backend)
        assert rep.exempt is None
        assert rep.ok, rep.summary()
        names = {c.name for c in rep.checks if not c.skipped}
        assert "flops/sparse-compaction" in names
        assert "scratch/gather-window" in names

    def test_3d_star_zero_violations(self):
        rep = audit.audit_context(_ctx((24, 48, 100), t=2, shape="star"),
                                  "fused_sparse_matmul")
        assert rep.ok, rep.summary()

    def _tampered(self, **replacements):
        ctx = _ctx((256, 512), t=2, shape="star")
        bd = registry.get_backend("fused_sparse_matmul")
        spec = bd.audit(ctx)
        bad = dataclasses.replace(spec.launches[0], **replacements)
        return ctx, bd, spec, bad

    def test_inflated_bands_shape_is_caught(self):
        """A wrong packed-row count (claiming fewer MXU FLOPs than the
        kernel executes) must fail the jaxpr-counted compaction proof,
        the structural mirror AND the gather-window bookkeeping."""
        ctx, bd, spec, l0 = self._tampered()
        bad = dataclasses.replace(
            l0, bands_shape=(l0.bands_shape[0] - 8, l0.bands_shape[1]))
        checks = audit.audit_flops(
            ctx, dataclasses.replace(spec, launches=(bad,)), bd.build(ctx))
        viol = {c.name for c in checks if not c.passed and not c.skipped}
        assert "flops/sparse-compaction" in viol
        assert "flops/structural" in viol
        gw = audit.audit_scratch(bad.launch_geometry(), bad)
        assert any(c.name == "scratch/gather-window" and not c.passed
                   for c in gw)

    def test_out_of_support_gather_is_caught(self):
        """A gather window escaping the dense band support [0, 2r] would
        read rows that do not exist -- scratch/gather-window flags it."""
        ctx, bd, spec, l0 = self._tampered()
        bad = dataclasses.replace(l0, band_lo=(99,) + l0.band_lo[1:])
        checks = audit.audit_scratch(bad.launch_geometry(), bad)
        assert any(c.name == "scratch/gather-window" and not c.passed
                   for c in checks)

    def test_span_mismatch_is_caught(self):
        ctx, bd, spec, l0 = self._tampered()
        bad = dataclasses.replace(l0, band_spans=(l0.band_spans[0] + 1,)
                                  + l0.band_spans[1:])
        checks = audit.audit_scratch(bad.launch_geometry(), bad)
        assert any(c.name == "scratch/gather-window" and not c.passed
                   for c in checks)

    def test_missing_metadata_is_caught(self):
        ctx, bd, spec, l0 = self._tampered()
        bad = dataclasses.replace(l0, band_lo=None, band_spans=None)
        checks = audit.audit_scratch(bad.launch_geometry(), bad)
        assert any(c.name == "scratch/gather-window" and not c.passed
                   for c in checks)


# ---------------------------------------------------------------------------
# Selector: the sparse sweet spot flips selection, explain == plan
# ---------------------------------------------------------------------------
FLIP = dict(grid=(256, 512), t=2, tile_n=32)


class TestSparseSelection:
    def test_star_flips_to_sparse(self):
        """At tile_n=32 the star kernel's kept-row fraction (S=0.9608)
        times the gather overhead beats the dense candidates on the
        compute-bound side -- the sparse unit flips the selection."""
        w = make_weights(StencilSpec("star", 2, 1), seed=1)
        base = dict(dtype_bytes=4, grid_shape=FLIP["grid"],
                    tile_n=FLIP["tile_n"])
        dense = explain(w, FLIP["t"], **base)
        d = explain(w, FLIP["t"], use_sparse_unit=True, **base)
        assert dense.backend != "fused_sparse_matmul"
        assert d.backend == "fused_sparse_matmul"
        assert "sparse sweet spot" in d.reason
        assert "S=" in d.reason

    def test_box_never_flips(self):
        """Box kernels compact to S = 1: the overhead term keeps the
        dense path ahead even with the sparse unit admitted."""
        w = make_weights(StencilSpec("box", 2, 1), seed=1)
        d = explain(w, FLIP["t"], dtype_bytes=4, grid_shape=FLIP["grid"],
                    tile_n=FLIP["tile_n"], use_sparse_unit=True)
        assert d.backend != "fused_sparse_matmul"

    def test_explain_matches_plan_decision(self):
        """Acceptance: ops.explain and the built plan report the same
        backend and the same sweet-spot boundary on the flip workload."""
        w = make_weights(StencilSpec("star", 2, 1), seed=1)
        d = explain(w, FLIP["t"], dtype_bytes=4, grid_shape=FLIP["grid"],
                    tile_n=FLIP["tile_n"], use_sparse_unit=True)
        p = stencil_plan(np.asarray(w), FLIP["grid"], np.float32, FLIP["t"],
                         tile_n=FLIP["tile_n"], use_sparse_unit=True,
                         interpret=True)
        assert p.backend == d.backend == "fused_sparse_matmul"
        assert p.decision.reason == d.reason

    def test_plan_key_includes_sparse_flag(self):
        """use_sparse_unit changes the selection, so it must be part of
        the plan cache key."""
        w = np.asarray(make_weights(StencilSpec("star", 2, 1), seed=1))
        base = lambda **kw: plan_signature(w, FLIP["grid"], np.float32,
                                           FLIP["t"], tile_n=FLIP["tile_n"],
                                           interpret=True, **kw)
        assert base(use_sparse_unit=True) != base(use_sparse_unit=False)
        assert base() == base(use_sparse_unit=False)


# ---------------------------------------------------------------------------
# Perfmodel: the sparse-banded unit's formulas and guards
# ---------------------------------------------------------------------------
class TestSparsePerfModel:
    def test_compaction_overhead(self):
        assert pm.compaction_overhead(128) == pytest.approx(1 / 256)
        assert pm.compaction_overhead(32) == pytest.approx(1 / 64)
        with pytest.raises(ValueError, match="positive"):
            pm.compaction_overhead(0)

    def test_kept_bounds_checked(self):
        w = pm.StencilWorkload(StencilSpec("star", 2, 1), 2, 4)
        for kept in (0.0, -0.5, 1.5):
            with pytest.raises(ValueError, match="kept"):
                pm.perf_sparse_banded(w, pm.TPU_V5E_BF16, 0.5, kept)
            with pytest.raises(ValueError, match="kept"):
                pm.perf_sparse_banded_reuse(w, pm.TPU_V5E_BF16, 0.5, kept)

    def test_mxu_fallback_peak(self):
        """Parts without a sparse unit price the compacted contraction
        on the plain MXU; kept=1 with zero overhead must then reproduce
        the dense matrix-reuse evaluation exactly."""
        w = pm.StencilWorkload(StencilSpec("star", 2, 1), 2, 4)
        sp = pm.perf_sparse_banded_reuse(w, pm.TPU_V5E_BF16, 0.5, 1.0, 0.0)
        dn = pm.perf_matrix_reuse(w, pm.TPU_V5E_BF16, 0.5)
        assert sp.raw_flops == pytest.approx(dn.raw_flops)
        assert sp.actual_flops == pytest.approx(dn.actual_flops)
        assert sp.raw_flops <= pm.TPU_V5E_BF16.p_matrix

    def test_sparse_unit_raises_ceiling(self):
        """On A100 the SpTC peak applies: a compute-bound compacted
        workload must strictly beat the dense matrix path."""
        hw = pm.A100_FLOAT
        assert hw.p_sparse is not None
        w = pm.StencilWorkload(StencilSpec("star", 2, 1), 8, 4)
        kept = 0.9
        sp = pm.perf_sparse_banded(w, hw, 0.5, kept)
        dn = pm.perf_matrix(w, hw, 0.5)
        if sp.bound is pm.Bound.COMPUTE and dn.bound is pm.Bound.COMPUTE:
            assert sp.actual_flops > dn.actual_flops
        assert pm._sparse_peak(hw, 4) == hw.p_sparse
        # MXU-only parts price the compaction at the operands' matrix peak
        v5e = pm.TPU_V5E_BF16
        assert pm._sparse_peak(v5e, 2) == v5e.p_matrix
        assert pm._sparse_peak(v5e, 4) == pm.matrix_peak(v5e, 4)
