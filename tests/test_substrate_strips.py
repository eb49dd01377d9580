"""Block-ring halo substrate: equivalence sweeps vs the jnp oracle, the
bit-for-bit equality of every ring of one grid with the ring pinned to
whole neighbor strips (``h_block = strip_m``), the intermediate-reuse MXU
regime's exactness guarantee, tiling validation error paths, and the
substrate's traffic accounting (1 + 2h/strip_m vs the pin's 3) -- plus
the N-D halo-plane generalization (DESIGN.md §9): 3D slab-substrate
equivalence (auto halo planes vs the whole-slab pin ``z_block = z_slab,
h_block = strip_m`` vs oracle), the 3D read-amplification product
formula, and the 1D lift through the 2D substrate."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import common
from repro.kernels.common import (SubstrateGeom, choose_col_blocks,
                                  choose_hblock, choose_slab_blocks,
                                  choose_strip, choose_strip_blocks,
                                  choose_tile, hbm_read_bytes_per_step_3d,
                                  resolve_substrate_geom,
                                  substrate_read_amp, validate_tiling,
                                  vmem_budget_bytes)
from repro.kernels.ref import stencil_direct_ref
from repro.kernels.stencil_direct import stencil_direct
from repro.kernels.stencil_matmul import stencil_matmul
from repro.stencil import StencilSpec, make_weights

RNG = np.random.default_rng(0)


def _x(h, w, dtype="float32"):
    x = jnp.asarray(RNG.normal(size=(h, w)).astype(np.float32))
    return x.astype(dtype)


def _x3(z, h, w, dtype="float32"):
    x = jnp.asarray(RNG.normal(size=(z, h, w)).astype(np.float32))
    return x.astype(dtype)


TOL = {"float32": 2e-4, "bfloat16": 6e-2}


class TestStripEquivalence:
    """New strip kernels vs ref.stencil_direct_ref across the ISSUE sweep:
    shape x r in {1,2,3} x t in {1..4} x dtype in {f32, bf16}."""

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t", [1, 2, 3, 4])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_fused_direct_matches_oracle(self, shape, r, t, dtype):
        spec = StencilSpec(shape, 2, r)
        w = make_weights(spec, seed=r)
        x = _x(48, 96, dtype)
        y = stencil_direct(x, w, t=t, tile_m=24, interpret=True)
        ref = stencil_direct_ref(x.astype(jnp.float32), w, t)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                                   atol=TOL[dtype])

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t", [1, 2, 3, 4])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_matmul_reuse_matches_oracle(self, shape, r, t, dtype):
        spec = StencilSpec(shape, 2, r)
        w = make_weights(spec, seed=r)
        x = _x(48, 96, dtype)
        y = stencil_matmul(x, w, t=t, tile_m=24, tile_n=32, interpret=True)
        ref = stencil_direct_ref(x.astype(jnp.float32), w, t)
        np.testing.assert_allclose(np.asarray(y, np.float32), np.asarray(ref),
                                   atol=TOL[dtype])

    def test_multi_strip_equals_single_strip(self):
        """Strip decomposition is invisible: gm=1 vs gm=4 bitwise equal."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(64, 64)
        a = stencil_direct(x, w, t=2, tile_m=64, interpret=True)
        b = stencil_direct(x, w, t=2, tile_m=16, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestSubblockedEquivalence:
    """Every ring of one grid assembles byte-identical extended strips, so
    its outputs are BIT-FOR-BIT equal to the ring pinned to whole neighbor
    strips (h_block = strip_m) in f32 -- box/star x r{1,2,3} x t{1,2,4} x
    h_block dividing strip_m."""

    STRIP_M = 24

    def _hblocks(self, r, t):
        halo = r * t
        return [d for d in (1, 2, 3, 4, 6, 8, 12, 24)
                if self.STRIP_M % d == 0 and d >= halo]

    @pytest.mark.parametrize("t", [1, 2, 4])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_direct_bitwise_vs_wholestrip(self, shape, r, t):
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        x = _x(48, 64)
        whole = stencil_direct(x, w, t=t, tile_m=self.STRIP_M,
                               h_block=self.STRIP_M, interpret=True)
        for hb in self._hblocks(r, t):
            sub = stencil_direct(x, w, t=t, tile_m=self.STRIP_M, h_block=hb,
                                 interpret=True)
            np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))

    @pytest.mark.parametrize("t", [1, 2, 4])
    @pytest.mark.parametrize("r", [1, 2, 3])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_matmul_bitwise_vs_wholestrip(self, shape, r, t):
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        x = _x(48, 64)
        whole = stencil_matmul(x, w, t=t, tile_m=self.STRIP_M, tile_n=32,
                               h_block=self.STRIP_M, interpret=True)
        for hb in self._hblocks(r, t):
            sub = stencil_matmul(x, w, t=t, tile_m=self.STRIP_M, tile_n=32,
                                 h_block=hb, interpret=True)
            np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))

    def test_single_strip_wraps_to_itself(self):
        """gm=1: the ring takes the periodic halo from the strip itself
        (modulo wrap), matching the oracle."""
        w = make_weights(StencilSpec("box", 2, 2), seed=0)
        x = _x(32, 32)
        ref = stencil_direct_ref(x, w, 2)
        y = stencil_direct(x, w, t=2, tile_m=32, h_block=8, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-4)

    def test_auto_hblock_end_to_end(self):
        """h_block=None auto-sizes (tile_m given and not) and still matches
        the oracle on a grid not divisible by 128."""
        w = make_weights(StencilSpec("star", 2, 1), seed=1)
        x = _x(192, 160)
        ref = stencil_direct_ref(x, w, 2)
        np.testing.assert_allclose(
            np.asarray(stencil_direct(x, w, t=2, interpret=True)),
            np.asarray(ref), atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(stencil_matmul(x, w, t=2, tile_m=48, interpret=True)),
            np.asarray(ref), atol=1e-4)


class TestSubstrate3D:
    """The 3D sweep: auto halo-plane blocks vs the whole-slab pin
    (z_block = z_slab, h_block = strip_m) vs the kernels/ref.py oracle,
    box/star x r{1,2} x t{1,2} x f32/bf16.  Both rings assemble
    byte-identical halo-extended slabs, so their outputs are BIT-for-bit
    equal in every dtype; the VPU box path even reproduces the roll
    oracle bitwise in f32 (identical tap order)."""

    Z, H, W = 12, 24, 32
    SLAB, STRIP = 6, 12

    TOL3 = {"float32": 2e-4, "bfloat16": 6e-2}

    def _blocks(self, halo):
        # unaligned (align=1) pins: interpret mode accepts any block, and
        # thin blocks exercise multi-block rings on these small grids
        return (choose_hblock(self.SLAB, halo, 1),
                choose_hblock(self.STRIP, halo, 1))

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t", [1, 2])
    @pytest.mark.parametrize("r", [1, 2])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_direct_bitwise_vs_wholeslab_and_oracle(self, shape, r, t, dtype):
        w = make_weights(StencilSpec(shape, 3, r), seed=r)
        x = _x3(self.Z, self.H, self.W, dtype)
        zb, hb = self._blocks(r * t)
        whole = stencil_direct(x, w, t=t, tile_m=self.STRIP,
                               h_block=self.STRIP, z_slab=self.SLAB,
                               z_block=self.SLAB, interpret=True)
        sub = stencil_direct(x, w, t=t, tile_m=self.STRIP, h_block=hb,
                             z_slab=self.SLAB, z_block=zb, interpret=True)
        np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))
        ref = stencil_direct_ref(x.astype(jnp.float32), w, t)
        if shape == "box" and dtype == "float32" and (r == 1 or t == 1):
            # no structural zero taps => identical accumulation order =>
            # the kernel IS the oracle, bit for bit (at r=2 AND t=2 XLA's
            # FMA formation on the intermediate diverges by 1 ulp)
            np.testing.assert_array_equal(np.asarray(sub), np.asarray(ref))
        else:
            np.testing.assert_allclose(np.asarray(sub, np.float32),
                                       np.asarray(ref),
                                       atol=self.TOL3[dtype])

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    @pytest.mark.parametrize("t", [1, 2])
    @pytest.mark.parametrize("r", [1, 2])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_matmul_bitwise_vs_wholeslab(self, shape, r, t, dtype):
        w = make_weights(StencilSpec(shape, 3, r), seed=r)
        x = _x3(self.Z, self.H, self.W, dtype)
        zb, hb = self._blocks(r * t)
        whole = stencil_matmul(x, w, t=t, tile_m=self.STRIP, tile_n=16,
                               h_block=self.STRIP, z_slab=self.SLAB,
                               z_block=self.SLAB, interpret=True)
        sub = stencil_matmul(x, w, t=t, tile_m=self.STRIP, tile_n=16,
                             h_block=hb, z_slab=self.SLAB, z_block=zb,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))
        ref = stencil_direct_ref(x.astype(jnp.float32), w, t)
        np.testing.assert_allclose(np.asarray(sub, np.float32),
                                   np.asarray(ref), atol=self.TOL3[dtype])

    def test_reuse_bitwise_vs_sequential_matmul_3d(self):
        """The 3D reuse regime executes the same banded dot products as t
        sequential contractions -- bit-for-bit in f32, as in 2D."""
        w = make_weights(StencilSpec("star", 3, 1), seed=0)
        x = _x3(12, 24, 32)
        fused = stencil_matmul(x, w, t=2, tile_m=12, tile_n=16,
                               z_slab=6, interpret=True)
        seq = x
        for _ in range(2):
            seq = stencil_matmul(seq, w, t=1, tile_m=12, tile_n=16,
                                 z_slab=6, interpret=True)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))

    def test_auto_geometry_end_to_end(self):
        """Fully auto (z_slab/tile_m/h_block/z_block all None) on a grid
        with no 128-divisible axis still matches the oracle."""
        w = make_weights(StencilSpec("box", 3, 1), seed=2)
        x = _x3(10, 20, 24)
        ref = stencil_direct_ref(x, w, 2)
        np.testing.assert_allclose(
            np.asarray(stencil_direct(x, w, t=2, interpret=True)),
            np.asarray(ref), atol=1e-4)
        np.testing.assert_allclose(
            np.asarray(stencil_matmul(x, w, t=2, interpret=True)),
            np.asarray(ref), atol=1e-4)

    def test_read_bytes_product_formula(self):
        """Analytic 3D reads == (1 + 2h/strip)(1 + 2zb/slab) * Z*H*W*D for
        every (z_block | z_slab, h_block | strip_m); the whole-slab pin
        reads exactly 9x."""
        Z, H, W, D = 16, 32, 64, 4
        grid_bytes = Z * H * W * D
        for zs, sm in [(8, 16), (16, 32), (4, 8)]:
            for zb in (d for d in range(1, zs + 1) if zs % d == 0):
                for hb in (d for d in range(1, sm + 1) if sm % d == 0):
                    g = SubstrateGeom(dim=3, strip_m=sm, h_block=hb,
                                      z_slab=zs, z_block=zb)
                    got = hbm_read_bytes_per_step_3d((Z, H, W), g, D)
                    want = ((1 + 2 * hb / sm) * (1 + 2 * zb / zs)
                            * grid_bytes)
                    assert got == pytest.approx(want)
                    assert g.read_amp == pytest.approx(got / grid_bytes)
            whole = SubstrateGeom(dim=3, strip_m=sm, h_block=sm,
                                  z_slab=zs, z_block=zs)
            assert hbm_read_bytes_per_step_3d((Z, H, W), whole, D) == \
                9 * grid_bytes
            assert whole.read_amp == 9.0

    def test_subblocked_amp_strictly_below_wholeslab(self):
        """Auto joint sizing reads less than the whole-slab pin's 9x
        (z_block = z_slab, h_block = strip_m), and its priced walk (ring
        reads plus grid steps, DESIGN.md §9) costs less than that pin's
        and no more than the thinnest-block ring of the same cells."""
        grid = (64, 256, 512)
        for halo in (1, 2, 4):
            zs, zb, sm, hb, wt, wb = choose_slab_blocks(*grid, halo)
            assert (wt, wb) == (0, 0)     # full width fits at this size
            g = SubstrateGeom(dim=3, strip_m=sm, h_block=hb,
                              z_slab=zs, z_block=zb)
            whole = SubstrateGeom(dim=3, strip_m=sm, h_block=sm,
                                  z_slab=zs, z_block=zs)
            thin = SubstrateGeom(dim=3, strip_m=sm,
                                 h_block=choose_hblock(sm, halo),
                                 z_slab=zs,
                                 z_block=choose_hblock(zs, halo, 1))
            assert whole.read_amp == 9.0
            assert g.read_amp < whole.read_amp
            cost = common.slab_walk_cost(grid, g, 4)
            assert cost < common.slab_walk_cost(grid, whole, 4)
            assert cost <= common.slab_walk_cost(grid, thin, 4)

    def test_band_sparsity_measures_every_rank(self):
        """The measured-S sanity helper covers the 1D/3D operands this PR
        adds (it measures exactly what the N-D kernel loads)."""
        from repro.kernels import band_sparsity
        for spec in (StencilSpec("box", 1, 1), StencilSpec("box", 2, 1),
                     StencilSpec("box", 3, 1), StencilSpec("star", 3, 2)):
            s = band_sparsity(make_weights(spec, seed=0), 32)
            assert 0.0 < s <= 1.0

    def test_choose_slab_blocks_divides_and_covers(self):
        for (z, h, halo) in [(64, 256, 3), (48, 96, 8), (16, 32, 4)]:
            zs, zb, sm, hb, wt, wb = choose_slab_blocks(z, h, 128, halo)
            assert z % zs == 0 and h % sm == 0
            assert zs % zb == 0 and sm % hb == 0
            assert zb >= halo and hb >= halo
            assert (wt, wb) == (0, 0)     # full width fits at this size

    def test_validate_errors(self):
        w = make_weights(StencilSpec("box", 3, 1), seed=0)
        with pytest.raises(ValueError, match="z_slab"):
            stencil_direct(_x3(12, 24, 32), w, tile_m=12, z_slab=5,
                           interpret=True)
        with pytest.raises(ValueError, match="z_block"):
            stencil_direct(_x3(12, 24, 32), w, t=2, tile_m=12, z_slab=6,
                           h_block=2, z_block=1, interpret=True)
        with pytest.raises(ValueError, match="z_block=z_slab"):
            resolve_substrate_geom((12, 24, 32), 1, 4, tile_m=12,
                                   h_block=2, z_slab=6, z_block=0)
        with pytest.raises(ValueError, match="rank"):
            stencil_direct(_x3(12, 24, 32), w[0], interpret=True)


class Test1DLift:
    """1D grids route through the 2D substrate lifted to (1, N): no crash,
    no vertical halo, read amplification exactly 1."""

    @pytest.mark.parametrize("t", [1, 3])
    @pytest.mark.parametrize("r", [1, 2])
    def test_direct_and_matmul_match_oracle(self, r, t):
        w = make_weights(StencilSpec("box", 1, r), seed=r)
        x = jnp.asarray(RNG.normal(size=(96,)).astype(np.float32))
        ref = stencil_direct_ref(x, w, t)
        np.testing.assert_allclose(
            np.asarray(stencil_direct(x, w, t=t, interpret=True)),
            np.asarray(ref), atol=2e-5)
        np.testing.assert_allclose(
            np.asarray(stencil_matmul(x, w, t=t, interpret=True)),
            np.asarray(ref), atol=2e-5)

    def test_lifted_geometry_reads_once(self):
        g = resolve_substrate_geom((128,), 0, 4)
        assert g.dim == 1 and g.strip_m == 1 and g.read_amp == 1.0

    def test_h_block_pins_coerce_like_plans(self):
        """Kernel-level h_block pins on 1D grids coerce exactly as the
        plan-level rule does (any positive pin becomes 1, 0 is refused)
        -- no pin a plan accepts may crash the kernel."""
        w = make_weights(StencilSpec("box", 1, 1), seed=0)
        x = jnp.asarray(RNG.normal(size=(64,)).astype(np.float32))
        base = stencil_direct(x, w, t=1, interpret=True)
        with pytest.raises(ValueError, match="h_block=strip_m"):
            resolve_substrate_geom(x.shape, 0, 4, h_block=0)
        for kernel in (stencil_direct, stencil_matmul):
            with pytest.raises(ValueError, match="h_block=strip_m"):
                kernel(x, w, t=1, h_block=0, interpret=True)
        for hb in (1, 4):
            np.testing.assert_array_equal(
                np.asarray(stencil_direct(x, w, t=1, h_block=hb,
                                          interpret=True)),
                np.asarray(base))
            np.testing.assert_array_equal(
                np.asarray(stencil_matmul(x, w, t=1, h_block=hb,
                                          interpret=True)),
                np.asarray(stencil_matmul(x, w, t=1, interpret=True)))


class TestChooseHBlock:
    def test_divides_and_covers_halo(self):
        for strip_m, halo in [(32, 1), (32, 4), (128, 8), (24, 12), (48, 5)]:
            hb = choose_hblock(strip_m, halo)
            assert strip_m % hb == 0 and hb >= halo

    def test_degenerates_to_whole_strip_at_full_halo(self):
        assert choose_hblock(32, 32) == 32
        assert substrate_read_amp(32, 32) == 3.0
        g = resolve_substrate_geom((64, 128), 32, 4, tile_m=32)
        assert g.h_block == 32 and g.read_amp == 3.0

    def test_amp_small_when_halo_allows(self):
        strip_m, hb = choose_strip_blocks(1024, 512, 2)
        assert substrate_read_amp(strip_m, hb) <= 1.25

    def test_joint_choice_consistent_with_choose_strip(self):
        for h, halo in [(256, 3), (96, 8), (128, 24)]:
            strip_m, hb = choose_strip_blocks(h, 512, halo)
            assert strip_m == choose_strip(h, 512, halo)
            assert strip_m % hb == 0 and hb >= halo


class TestReuseRegimeExactness:
    """The intermediate-reuse kernel executes the SAME per-point banded dot
    products as t sequential MXU steps, so in f32 it is bit-for-bit equal
    to the sequential-matmul execution (no alpha redundancy to perturb
    rounding) -- the strongest equivalence the regime admits."""

    @pytest.mark.parametrize("r,t", [(1, 2), (1, 4), (2, 3), (3, 2)])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_bitwise_vs_sequential_matmul(self, shape, r, t):
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        x = _x(64, 64)
        fused = stencil_matmul(x, w, t=t, tile_m=32, tile_n=32, interpret=True)
        seq = x
        for _ in range(t):
            seq = stencil_matmul(seq, w, t=1, tile_m=32, tile_n=32,
                                 interpret=True)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))


class TestValidateTiling:
    def test_rows_not_divisible(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        with pytest.raises(ValueError, match="divisible"):
            stencil_direct(_x(60, 64), w, tile_m=32, interpret=True)

    def test_cols_not_divisible_matmul_runs_remainder(self):
        """tile_n no longer needs to divide W: the final narrower chunk
        contracts against the banded operand's leading submatrix (the
        choose_tile cap-policy satellite) and matches the oracle."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(64, 60)
        y = stencil_matmul(x, w, tile_m=32, tile_n=32, interpret=True)
        ref = stencil_direct_ref(x, w, 1)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=2e-5)
        with pytest.raises(ValueError, match="column tile"):
            stencil_matmul(x, w, tile_m=32, tile_n=0, interpret=True)

    def test_halo_exceeds_strip(self):
        w = make_weights(StencilSpec("box", 2, 3), seed=0)
        with pytest.raises(ValueError, match="halo"):
            stencil_direct(_x(64, 64), w, t=6, tile_m=16, interpret=True)

    def test_halo_exceeds_width(self):
        with pytest.raises(ValueError, match="width"):
            validate_tiling((32, 8), 16, 8, 9)

    def test_valid_passes(self):
        validate_tiling((64, 128), 32, 32, 4)
        validate_tiling((64, 128), 32, 32, 4, h_block=8)

    def test_hblock_not_dividing_strip(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        with pytest.raises(ValueError, match="h_block"):
            stencil_direct(_x(64, 64), w, tile_m=32, h_block=5,
                           interpret=True)

    def test_zero_block_pins_name_the_ring_pin(self):
        """h_block=0 and z_block=0 name no block ring: every entry point
        (kernel, plan, grid-free pricing) refuses them with the pin that
        reads the same whole strips or slabs."""
        from repro.kernels import explain, stencil_plan
        w2 = make_weights(StencilSpec("box", 2, 1), seed=0)
        w3 = make_weights(StencilSpec("star", 3, 1), seed=0)
        strip = "pin h_block=strip_m"
        slab = "pin z_block=z_slab"
        with pytest.raises(ValueError, match=strip):
            stencil_direct(_x(64, 64), w2, tile_m=32, h_block=0,
                           interpret=True)
        with pytest.raises(ValueError, match=strip):
            stencil_plan(w2, (64, 64), np.float32, 1, h_block=0,
                         interpret=True, use_cache=False)
        with pytest.raises(ValueError, match=strip):
            explain(w2, 1, h_block=0)
        with pytest.raises(ValueError, match=slab):
            stencil_matmul(_x3(12, 24, 32), w3, tile_m=12, z_slab=6,
                           z_block=0, interpret=True)
        with pytest.raises(ValueError, match=slab):
            stencil_plan(w3, (12, 24, 32), np.float32, 1, z_block=0,
                         interpret=True, use_cache=False)
        with pytest.raises(ValueError, match=slab):
            explain(w3, 1, z_block=0)

    def test_hblock_smaller_than_halo(self):
        w = make_weights(StencilSpec("box", 2, 2), seed=0)
        with pytest.raises(ValueError, match="h_block"):
            stencil_matmul(_x(64, 64), w, t=2, tile_m=32, tile_n=32,
                           h_block=2, interpret=True)


class TestChooseStrip:
    def test_divides_and_covers_halo(self):
        for h, halo in [(256, 3), (96, 8), (128, 24)]:
            s = choose_strip(h, 512, halo)
            assert h % s == 0 and s >= halo

    def test_prefers_mxu_height(self):
        assert choose_strip(1024, 512, 2) == 128

    def test_vmem_pressure_shrinks_strip(self):
        big = choose_strip(4096, 4096, 1, vmem_budget=2**40)
        small = choose_strip(4096, 4096, 1, vmem_budget=2**20)
        assert small < big

    def test_small_grid_single_strip(self):
        assert choose_strip(32, 32, 4) == 32

    def test_auto_tiles_in_dispatch(self):
        """tile_m=None routes through choose_strip/choose_tile: grids not
        divisible by 128 work out of the box."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(192, 160)                     # 192 % 128 != 0, 160 % 128 != 0
        ref = stencil_direct_ref(x, w, 2)
        yd = stencil_direct(x, w, t=2, interpret=True)
        ym = stencil_matmul(x, w, t=2, interpret=True)
        np.testing.assert_allclose(np.asarray(yd), np.asarray(ref), atol=1e-4)
        np.testing.assert_allclose(np.asarray(ym), np.asarray(ref), atol=1e-4)

    def test_narrow_grid_deep_fusion(self):
        """Width only constrains the per-step wrap radius r, not t*r: a
        16-wide grid takes t=8 fused steps of an r=3 stencil."""
        w = make_weights(StencilSpec("box", 2, 3), seed=0)
        x = _x(64, 16)
        ref = stencil_direct_ref(x, w, 8)
        y = stencil_direct(x, w, t=8, tile_m=32, interpret=True)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref), atol=1e-3)


class TestTrafficAccounting:
    """Analytic reads of the ring: 9x at the whole-slab pin, 3x at the
    whole-strip pin, 1 + 2h/strip_m at the auto halo blocks."""

    def test_loads_per_output_tile(self):
        """Block loads per output strip (the ring length): 3 at the
        whole-strip pin, strip_m/h_block + 2 below it, 3x3 at the 3D
        whole-slab pin."""
        whole = common.strip_launch_geometry((128, 128), 32, 32, 4)
        sub = common.strip_launch_geometry((128, 128), 32, 8, 4)
        assert whole.ring == 3 and sub.ring == 32 // 8 + 2
        slab = common.slab_launch_geometry(
            (16, 64, 128), SubstrateGeom(dim=3, strip_m=32, h_block=32,
                                         z_slab=8, z_block=8), 4)
        assert slab.ring == 9

    def test_read_amplification_3x_vs_9x(self):
        shape = (256, 256)
        strip = common.hbm_read_bytes_per_step(shape, 32, 4, h_block=32)
        slab = hbm_read_bytes_per_step_3d(
            (16,) + shape, SubstrateGeom(dim=3, strip_m=32, h_block=32,
                                         z_slab=8, z_block=8), 4)
        grid_bytes = 256 * 256 * 4
        assert strip == 3 * grid_bytes
        assert slab == 9 * 16 * grid_bytes

    def test_subblocked_read_bytes_formula(self):
        """Analytic read_bytes == (1 + 2h/strip_m) * H*W*D exactly, for
        every h_block dividing the strip."""
        H, W, D = 256, 256, 4
        grid_bytes = H * W * D
        for strip_m in (32, 64, 128):
            for hb in (d for d in range(1, strip_m + 1) if strip_m % d == 0):
                got = common.hbm_read_bytes_per_step((H, W), strip_m, D,
                                                     h_block=hb)
                want = (1 + 2 * hb / strip_m) * grid_bytes
                assert got == want
                assert substrate_read_amp(strip_m, hb) == \
                    pytest.approx(got / grid_bytes)

    def test_subblocked_amp_at_default_strips(self):
        """At default joint sizing the amplification is <= 1.3x for shallow
        halos (the ISSUE's acceptance bound vs 3.0x whole-strip)."""
        for halo in (1, 2, 4):
            strip_m, hb = choose_strip_blocks(1024, 1024, halo)
            assert substrate_read_amp(strip_m, hb) <= 1.3
        assert substrate_read_amp(strip_m, strip_m) == 3.0   # whole-strip pin
        with pytest.raises(ValueError, match="auto"):
            substrate_read_amp(strip_m, None)             # None is not a ring
        with pytest.raises(ValueError, match="no block ring"):
            substrate_read_amp(strip_m, 0)

    def test_bands_charged_identically(self):
        """The banded operand term is independent of the ring's blocks
        (one fetch per output strip)."""
        bands = (3, 40, 32)
        base = common.hbm_read_bytes_per_step((256, 256), 32, 4, h_block=32)
        with_b = common.hbm_read_bytes_per_step((256, 256), 32, 4,
                                                bands_shape=bands,
                                                h_block=32)
        sub = common.hbm_read_bytes_per_step((256, 256), 32, 4, h_block=8)
        sub_b = common.hbm_read_bytes_per_step((256, 256), 32, 4,
                                               bands_shape=bands, h_block=8)
        assert with_b - base == sub_b - sub == 8 * 3 * 40 * 32 * 4


class TestChooseTile:
    """The choose_tile bugfix satellite: pad-or-cap policy, never a
    degenerate tile (the old largest-divisor rule returned 1 on primes
    and off-lane divisors like 65 on near-misses)."""

    def test_never_degenerate_sweep(self):
        """The acceptance sweep: for every n <= 4096 the tile is
        min(n, 128) -- never below min(n, 8), never above n."""
        for n in range(1, 4097):
            tile = choose_tile(n)
            assert tile == min(n, 128)
            assert tile >= min(n, 8)
            assert tile <= n

    def test_issue_cases(self):
        assert choose_tile(257) == 128        # was 1 (prime width)
        assert choose_tile(130) == 128        # was 65 (off-lane divisor)
        assert choose_tile(100) == 100
        assert choose_tile(4096) == 128
        assert choose_tile(300, preferred=256) == 256

    def test_rejects_nonpositive(self):
        with pytest.raises(ValueError, match="positive"):
            choose_tile(0)


class TestChooseHBlockProperty:
    """The choose_hblock satellite: integer ceil-division floor, plus the
    exhaustive property sweep (divides the strip, covers the halo)."""

    def test_property_sweep(self):
        for strip_m in range(1, 129):
            for halo in range(0, strip_m + 1):
                hb = choose_hblock(strip_m, halo)
                assert isinstance(hb, int)
                assert strip_m % hb == 0, (strip_m, halo, hb)
                assert hb >= halo, (strip_m, halo, hb)
                # the TPU tiling rule: a sublane multiple or the strip
                assert hb % 8 == 0 or hb == strip_m, (strip_m, halo, hb)
                # the 1/16 floor is integer ceil division
                assert hb >= min(strip_m, -(-strip_m // 16))

    def test_floor_is_integer_ceil(self):
        # strip_m=24: ceil(24/16)=2; the smallest halo-0 divisor >= 2 is 2
        # on an untiled axis, and one 8-row sublane tile on the row axis
        assert choose_hblock(24, 0, 1) == 2
        assert choose_hblock(32, 0, 1) == 2
        assert choose_hblock(24, 0) == 8
        assert choose_hblock(256, 0) == 16    # the 1/16 floor binds
        assert choose_hblock(17, 0) == 17     # prime: no proper divisor


class TestWrapRadiusGuard:
    """The shared wrap-radius guard satellite: one check, every rank
    (the 1D/2D/3D branches used to carry their own copies; the 3D path
    was untested)."""

    @pytest.mark.parametrize("shape,kwargs", [
        ((8,), {}),
        ((32, 8), {}),
        ((16, 32, 8), dict(z_slab=16)),
    ])
    def test_all_ranks_raise(self, shape, kwargs):
        with pytest.raises(ValueError, match="wrap radius"):
            validate_tiling(shape, 16, 8, 9, **kwargs)

    def test_3d_kernel_path(self):
        w = make_weights(StencilSpec("box", 3, 3), seed=0)
        with pytest.raises(ValueError, match="wrap radius"):
            stencil_direct(_x3(8, 16, 2), w, tile_m=8, z_slab=8,
                           interpret=True)

    def test_valid_radius_passes(self):
        validate_tiling((8,), 1, 8, 4)
        validate_tiling((16, 32, 32), 16, 32, 4, z_slab=16)


class TestColumnTiled:
    """The PR's tentpole: the column-tiled W substrate (DESIGN.md §10).
    Substrate equivalence (column-tiled vs whole-width), the remainder
    path on awkward widths, the three-factor traffic formula, and the
    auto sizing's budget-driven escalation."""

    #: Awkward widths of the ISSUE's acceptance sweep: prime, composite
    #: with no 128-friendly divisor, and 8-divisible-but-not-128.
    AWKWARD_W = (257, 300, 1000)

    @pytest.mark.parametrize("wid", (64,) + AWKWARD_W)
    @pytest.mark.parametrize("r", [1, 2])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_direct_t1_vs_wholewidth(self, shape, r, wid):
        """Single-step VPU: column-tiled (aligned AND remainder paths) is
        BIT-for-bit the whole-width kernel in f32 for box kernels (no
        structural zero taps => same tap sequence => same FMA formation);
        star kernels' skipped taps let XLA contract differently on some
        widths, perturbing the last ulp (the seed 3D-oracle caveat)."""
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        x = _x(48, wid)
        whole = stencil_direct(x, w, t=1, tile_m=24, h_block=12,
                               interpret=True)
        sub = stencil_direct(x, w, t=1, tile_m=24, h_block=12, w_tile=32,
                             interpret=True)
        if shape == "box":
            np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))
        else:
            np.testing.assert_allclose(np.asarray(sub), np.asarray(whole),
                                       atol=1e-6)

    @pytest.mark.parametrize("wid", (64,) + AWKWARD_W)
    @pytest.mark.parametrize("r,t", [(1, 1), (1, 2), (2, 2), (3, 4)])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_matmul_bitwise_vs_wholewidth(self, shape, r, t, wid):
        """The MXU banded path is BIT-for-bit equal between the
        column-tiled and whole-width substrates at every depth, aligned
        and remainder widths alike: each output column contracts the
        same taps against the same band column, and zero band entries
        are exact no-ops -- the satellite's substrate-equivalence sweep
        on W in {257, 300, 1000}."""
        w = make_weights(StencilSpec(shape, 2, r), seed=r)
        x = _x(48, wid)
        whole = stencil_matmul(x, w, t=t, tile_m=24, h_block=12,
                               interpret=True)
        sub = stencil_matmul(x, w, t=t, tile_m=24, h_block=12, w_tile=32,
                             interpret=True)
        np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))

    @pytest.mark.parametrize("wid", (64, 257, 300))
    @pytest.mark.parametrize("r,t", [(1, 2), (2, 2), (1, 4)])
    def test_direct_depth_close_vs_wholewidth(self, r, t, wid):
        """Fused VPU steps: the carried-x-halo graph differs from the
        re-wrap graph, so XLA's FMA formation may perturb the last ulp
        (exactly the seed caveat for the 3D oracle at r=2, t=2) -- the
        values agree to float32 resolution."""
        w = make_weights(StencilSpec("star", 2, r), seed=r)
        x = _x(48, wid)
        whole = stencil_direct(x, w, t=t, tile_m=24, h_block=12,
                               interpret=True)
        sub = stencil_direct(x, w, t=t, tile_m=24, h_block=12, w_tile=32,
                             interpret=True)
        np.testing.assert_allclose(np.asarray(sub), np.asarray(whole),
                                   atol=1e-6)

    @pytest.mark.parametrize("wid", [32, 37, 257])
    @pytest.mark.parametrize("shape", ["box", "star"])
    def test_3d_column_tiled(self, shape, wid):
        """3D slab substrate with a column-tiled W: matmul bit-for-bit vs
        whole-width, direct bitwise at t=1 for box (allclose for star --
        see test_direct_t1_vs_wholewidth) and oracle-close at depth."""
        w = make_weights(StencilSpec(shape, 3, 1), seed=1)
        x = _x3(12, 24, wid)
        pins = dict(tile_m=12, z_slab=6, h_block=2, z_block=2,
                    interpret=True)
        whole = stencil_direct(x, w, t=1, **pins)
        sub = stencil_direct(x, w, t=1, w_tile=16, **pins)
        if shape == "box":
            np.testing.assert_array_equal(np.asarray(sub), np.asarray(whole))
        else:
            np.testing.assert_allclose(np.asarray(sub), np.asarray(whole),
                                       atol=1e-6)
        mw = stencil_matmul(x, w, t=2, tile_n=16, **pins)
        ms = stencil_matmul(x, w, t=2, tile_n=16, w_tile=16, **pins)
        np.testing.assert_array_equal(np.asarray(ms), np.asarray(mw))
        ref = stencil_direct_ref(x, w, 2)
        np.testing.assert_allclose(np.asarray(ms), np.asarray(ref),
                                   atol=2e-4)

    def test_reuse_bitwise_vs_sequential_column_tiled(self):
        """The reuse regime's exactness guarantee survives column tiling:
        t fused radius-r contractions == t sequential launches, bitwise."""
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(48, 64)
        fused = stencil_matmul(x, w, t=3, tile_m=24, h_block=12, w_tile=32,
                               interpret=True)
        seq = x
        for _ in range(3):
            seq = stencil_matmul(seq, w, t=1, tile_m=24, h_block=12,
                                 w_tile=32, interpret=True)
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(seq))

    def test_wide_grid_exceeding_budget_executes_bitwise(self, monkeypatch):
        """THE acceptance criterion: 2D and 3D grids whose FULL-WIDTH
        working set exceeds the VMEM budget execute through auto
        resolution (which column-tiles), bit-for-bit equal to the
        reference oracle in f32, with the resolved geometry carrying a
        positive w_tile."""
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "16384")
        assert vmem_budget_bytes() == 16384

        # 2D: even the thinnest full-width strip needs ~33 KB > budget
        assert min(common._strip_working_set(d, choose_hblock(d, 1),
                                             1024, 1, 4)
                   for d in (1, 2, 4, 8, 16, 32)) > 16384
        g2 = resolve_substrate_geom((32, 1024), 1, 4)
        assert g2.w_tile > 0 and g2.w_block >= 1
        w = make_weights(StencilSpec("box", 2, 1), seed=3)
        x = _x(32, 1024)
        ref = stencil_direct_ref(x, w, 1)
        y = stencil_direct(x, w, t=1, interpret=True)     # all-auto
        np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))

        # 3D
        g3 = resolve_substrate_geom((8, 16, 512), 1, 4)
        assert g3.w_tile > 0
        w3 = make_weights(StencilSpec("box", 3, 1), seed=3)
        x3 = _x3(8, 16, 512)
        ref3 = stencil_direct_ref(x3, w3, 1)
        y3 = stencil_direct(x3, w3, t=1, interpret=True)  # all-auto
        np.testing.assert_array_equal(np.asarray(y3), np.asarray(ref3))

    def test_auto_stays_fullwidth_when_it_fits(self):
        """Default budget, modest widths: the resolution never
        column-tiles, so every pre-existing geometry is unchanged."""
        for shape in [(192, 160), (64, 64), (256, 512)]:
            g = resolve_substrate_geom(shape, 2, 4)
            assert g.w_tile == 0 and g.w_block == 0
        g = resolve_substrate_geom((12, 24, 32), 2, 4)
        assert g.w_tile == 0

    def test_read_bytes_three_factor_formula(self):
        """Analytic reads == (1 + 2h/strip)(1 + 2wb/wt) * H*W*D in 2D and
        the (z, y, w) product in 3D, exactly, for aligned widths."""
        H, W, D = 64, 256, 4
        grid_bytes = H * W * D
        for sm, hb in [(16, 4), (32, 8)]:
            for wt, wb in [(32, 8), (64, 16), (128, 32)]:
                got = common.hbm_read_bytes_per_step(
                    (H, W), sm, D, h_block=hb, w_tile=wt, w_block=wb)
                want = (1 + 2 * hb / sm) * (1 + 2 * wb / wt) * grid_bytes
                assert got == pytest.approx(want)
                g = SubstrateGeom(dim=2, strip_m=sm, h_block=hb,
                                  w_tile=wt, w_block=wb)
                assert g.read_amp == pytest.approx(got / grid_bytes)
        Z = 16
        grid_bytes3 = Z * H * W * D
        g3 = SubstrateGeom(dim=3, strip_m=16, h_block=4, z_slab=8,
                           z_block=2, w_tile=64, w_block=16)
        got3 = hbm_read_bytes_per_step_3d((Z, H, W), g3, D)
        want3 = ((1 + 2 * 4 / 16) * (1 + 2 * 2 / 8) * (1 + 2 * 16 / 64)
                 * grid_bytes3)
        assert got3 == pytest.approx(want3)
        assert g3.read_amp == pytest.approx(got3 / grid_bytes3)

    def test_choose_col_blocks_divides_and_covers(self):
        for (h, wid, halo) in [(64, 4096, 2), (128, 1000, 3), (32, 257, 1)]:
            sm, hb, wt, wb = choose_col_blocks(h, wid, halo,
                                               vmem_budget=64 * 1024)
            assert h % sm == 0 and sm % hb == 0 and hb >= halo
            assert wt % wb == 0 and wb >= halo and 0 < wt < wid

    def test_validate_errors(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(48, 64)
        with pytest.raises(ValueError, match="does not divide w_tile"):
            stencil_direct(x, w, tile_m=24, h_block=12, w_tile=32,
                           w_block=5, interpret=True)
        w2 = make_weights(StencilSpec("box", 2, 2), seed=0)
        with pytest.raises(ValueError, match="x-halo"):
            stencil_direct(x, w2, t=2, tile_m=24, h_block=12, w_tile=32,
                           w_block=2, interpret=True)
        with pytest.raises(ValueError, match="h_block=strip_m"):
            stencil_direct(x, w, tile_m=24, h_block=0, w_tile=32,
                           interpret=True)
        with pytest.raises(ValueError, match="w_tile"):
            resolve_substrate_geom((48, 64), 1, 4, w_block=8)

    def test_lone_wblock_rejected_on_every_path(self, monkeypatch):
        """A w_block pin without a w_tile is rejected uniformly: its
        acceptance must not flip when the VMEM budget forces the
        column-tiled escalation (the auto w_tile need not be divisible
        by an arbitrary pinned block)."""
        monkeypatch.setenv("REPRO_VMEM_BUDGET", "16384")
        with pytest.raises(ValueError, match="w_tile"):
            resolve_substrate_geom((32, 1024), 1, 4, w_block=5)
        with pytest.raises(ValueError, match="w_tile"):
            resolve_substrate_geom((8, 16, 512), 1, 4, w_block=5)
        with pytest.raises(ValueError, match="exceeds grid width"):
            validate_tiling((48, 64), 24, 64, 1, h_block=12, w_tile=128,
                            w_block=8)
        # a zero block pin is refused under column tiling in 3D too
        with pytest.raises(ValueError, match="h_block=strip_m"):
            resolve_substrate_geom((12, 24, 32), 1, 4, tile_m=12,
                                   z_slab=6, h_block=0, w_tile=16)

    def test_wtile_at_grid_width_is_fullwidth_fast_path(self):
        """w_tile >= W normalizes to the full-width fast path: identical
        geometry, identical (bitwise) results."""
        g = resolve_substrate_geom((48, 64), 1, 4, w_tile=64)
        assert g.w_tile == 0
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = _x(48, 64)
        a = stencil_direct(x, w, tile_m=24, interpret=True)
        b = stencil_direct(x, w, tile_m=24, w_tile=64, interpret=True)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
