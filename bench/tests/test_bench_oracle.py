"""The benchmark's copy of the oracle and the weights stays equal to the
program's originals, so that drift in either shows; and the banded
comparison reads what a whole-grid one does."""
import jax.numpy as jnp
import numpy as np
import pytest

from bench import oracle

SPECS = [("box", 2, 1), ("star", 2, 1), ("box", 2, 3), ("star", 3, 1),
         ("box", 3, 1), ("star", 1, 2)]


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("seed", [0, 7])
def test_weights_match_the_program(spec, seed):
    from repro.stencil import StencilSpec, make_weights
    ours = oracle.make_weights(*spec, seed=seed)
    theirs = make_weights(StencilSpec(*spec), seed=seed)
    assert ours.dtype == theirs.dtype
    np.testing.assert_array_equal(ours, theirs)
    np.testing.assert_array_equal(oracle.support_mask(*spec),
                                  StencilSpec(*spec).support_mask())


@pytest.mark.parametrize("spec", SPECS)
@pytest.mark.parametrize("boundary", ["periodic", "reflect"])
def test_oracle_matches_the_program(spec, boundary):
    from repro.stencil.reference import apply_stencil_steps
    dim = spec[1]
    shape = {1: (40,), 2: (24, 20), 3: (10, 12, 9)}[dim]
    x = jnp.asarray(np.random.default_rng(3).normal(size=shape),
                    jnp.float32)
    w = oracle.make_weights(*spec, seed=1)
    ours = oracle.apply_stencil_steps(x, jnp.asarray(w), 3,
                                      (boundary,) * dim)
    theirs = apply_stencil_steps(x, jnp.asarray(w), 3, boundary)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(theirs))


@pytest.mark.parametrize("band", [8, 16, 48])
def test_banded_errors_read_the_whole_grid(band):
    import jax
    dev = jax.devices()[0]
    w = oracle.make_weights("box", 2, 1, seed=2)
    x = jnp.asarray(np.random.default_rng(0).normal(size=(48, 32)),
                    jnp.float32)
    modes = ("periodic", "periodic")
    ref = oracle.apply_stencil_steps(x, jnp.asarray(w), 2, modes)
    y = ref.at[37, 5].add(0.5)
    err, top = oracle.banded_errors(x, y, w, 2, modes, band, dev)
    assert err == pytest.approx(0.5, rel=1e-6)
    assert top == pytest.approx(float(jnp.max(jnp.abs(ref))), rel=1e-6)
    err0, _ = oracle.banded_errors(x, ref, w, 2, modes, band, dev)
    assert err0 == 0.0
    low, _ = oracle.banded_errors(x, ref, w, 2, modes, band, dev, low=True)
    assert 1e-4 < low / top < 5e-2       # bfloat16 in the program's place


def test_banded_errors_refuse_an_uneven_band():
    import jax
    w = oracle.make_weights("box", 2, 1)
    x = jnp.zeros((48, 32), jnp.float32)
    with pytest.raises(ValueError, match="does not divide"):
        oracle.banded_errors(x, x, w, 1, ("periodic",) * 2, 10,
                             jax.devices()[0])
