"""Stencil domain: specs, weights, fusion composition, references."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.stencil import (StencilSpec, box, star, make_weights,
                           jacobi_weights, fuse_weights, fused_num_points)
from repro.stencil.reference import (apply_stencil, apply_stencil_steps,
                                     apply_stencil_conv)


class TestSpec:
    def test_num_points(self):
        assert box(2, 1).num_points == 9
        assert box(2, 7).num_points == 225
        assert box(3, 1).num_points == 27
        assert star(2, 1).num_points == 5
        assert star(3, 2).num_points == 13

    def test_names(self):
        assert box(2, 1).name == "Box-2D1R"
        assert StencilSpec.from_name("Star-3D2R") == star(3, 2)

    def test_support_mask(self):
        m = star(2, 1).support_mask()
        assert m.sum() == 5 and m[1, 1] and m[0, 1] and not m[0, 0]

    def test_validation(self):
        with pytest.raises(ValueError):
            StencilSpec("hex", 2, 1)
        with pytest.raises(ValueError):
            StencilSpec("box", 0, 1)
        with pytest.raises(ValueError):
            StencilSpec("box", 2, 0)

    def test_intensity(self):
        assert box(2, 1).arithmetic_intensity(4) == 9 / 4


class TestWeights:
    def test_star_weights_masked(self):
        w = make_weights(star(2, 2), seed=0)
        assert np.count_nonzero(w) == 9
        assert w.sum() == pytest.approx(1.0, abs=1e-5)

    def test_fused_radius(self):
        w = make_weights(box(2, 1), seed=0)
        assert fuse_weights(w, 3).shape == (7, 7)

    @given(shape=st.sampled_from(["box", "star"]), d=st.integers(1, 2),
           r=st.integers(1, 2), t=st.integers(1, 4))
    @settings(max_examples=30, deadline=None)
    def test_fused_application_equals_sequential(self, shape, d, r, t):
        """Core linearity property behind the paper's kernel fusion."""
        spec = StencilSpec(shape, d, r)
        w = make_weights(spec, seed=1, dtype=np.float64)
        n = 32
        x = jnp.asarray(np.random.default_rng(0).normal(size=(n,) * d))
        seq = apply_stencil_steps(x, jnp.asarray(w), t)
        fused = apply_stencil(x, jnp.asarray(fuse_weights(w, t)))
        # jax computes in f32 (x64 disabled): tolerance is f32-scale
        np.testing.assert_allclose(np.asarray(seq), np.asarray(fused),
                                   rtol=2e-5, atol=2e-5)

    def test_fused_num_points_matches_support(self):
        for spec in (box(2, 1), star(2, 1), star(3, 1), box(3, 1)):
            for t in (1, 2, 3):
                w = jacobi_weights(spec, np.float64)
                assert fused_num_points(spec, t) == \
                    np.count_nonzero(fuse_weights(w, t))


class TestReference:
    @pytest.mark.parametrize("boundary", ["periodic", "zero"])
    @pytest.mark.parametrize("d", [1, 2, 3])
    def test_conv_oracle_agrees(self, boundary, d):
        spec = StencilSpec("box", d, 1)
        w = make_weights(spec, seed=2)
        n = 16
        x = jnp.asarray(np.random.default_rng(1).normal(size=(n,) * d)
                        .astype(np.float32))
        a = apply_stencil(x, jnp.asarray(w), boundary)
        b = apply_stencil_conv(x, jnp.asarray(w), boundary)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("d", [1, 3])
    def test_roll_vs_conv_cross_check_f32(self, d, shape):
        """The satellite's 1D/3D oracle cross-check: the roll-based and
        conv-based references agree through the single N-D path (no more
        per-rank special cases in _offsets / apply_stencil_conv)."""
        spec = StencilSpec(shape, d, 2)
        w = make_weights(spec, seed=3)
        x = jnp.asarray(np.random.default_rng(4).normal(size=(12,) * d)
                        .astype(np.float32))
        for boundary in ("periodic", "zero"):
            a = apply_stencil(x, jnp.asarray(w), boundary)
            b = apply_stencil_conv(x, jnp.asarray(w), boundary)
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("shape", ["box", "star"])
    @pytest.mark.parametrize("d", [1, 3])
    def test_roll_vs_conv_cross_check_f64(self, d, shape):
        """Same cross-check at f64: tolerances tighten by ~8 orders of
        magnitude, catching any dtype-dependent path divergence."""
        with jax.enable_x64(True):
            spec = StencilSpec(shape, d, 1)
            w = make_weights(spec, seed=5, dtype=np.float64)
            x = jnp.asarray(np.random.default_rng(6).normal(size=(10,) * d))
            assert x.dtype == jnp.float64
            for boundary in ("periodic", "zero"):
                a = apply_stencil(x, jnp.asarray(w), boundary)
                b = apply_stencil_conv(x, jnp.asarray(w), boundary)
                assert a.dtype == jnp.float64
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=1e-13, atol=1e-13)

    def test_rank_mismatch_raises(self):
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        x = jnp.zeros((8, 8, 8), np.float32)
        with pytest.raises(ValueError, match="rank"):
            apply_stencil(x, jnp.asarray(w))
        with pytest.raises(ValueError, match="rank"):
            apply_stencil_conv(x, jnp.asarray(w))

    def test_jacobi_converges_to_mean(self):
        # repeated Jacobi smoothing with periodic BC converges to the mean
        spec = box(2, 1)
        w = jacobi_weights(spec)
        x = jnp.asarray(np.random.default_rng(2).normal(size=(16, 16))
                        .astype(np.float32))
        y = apply_stencil_steps(x, jnp.asarray(w), 200)
        np.testing.assert_allclose(np.asarray(y), float(x.mean()), atol=1e-3)
