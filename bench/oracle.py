"""The yardstick's own stencil oracle and weights.

A copy of the program's XLA oracle (``repro.stencil.reference``) and of
``make_weights`` (``repro.stencil.weights``), kept here so that no change
to the program can move what ``correct`` is measured against.  A test
(``bench/tests/test_bench_oracle.py``) holds the copy equal to the
originals at a tiny size, so drift in either shows.

``banded_errors`` runs the oracle over a grid ``band`` rows at a time
(each band extended by the ``t * r`` rows it depends on), on one device,
so that a grid whose whole-grid oracle would not fit beside the
program's arrays is still checked on the chip.
"""
from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np

#: ``jnp.pad`` mode implementing each boundary mode.
PAD_MODE = {"periodic": "wrap", "zero": "constant",
            "reflect": "reflect", "replicate": "edge"}


def support_mask(shape: str, dim: int, radius: int) -> np.ndarray:
    """Boolean mask of a box or star stencil inside its enclosing box."""
    width = 2 * radius + 1
    if shape == "box":
        return np.ones((width,) * dim, dtype=bool)
    if shape != "star":
        raise ValueError(f"unknown stencil shape {shape!r}")
    mask = np.zeros((width,) * dim, dtype=bool)
    center = (radius,) * dim
    mask[center] = True
    for axis in range(dim):
        idx = list(center)
        for off in range(-radius, radius + 1):
            idx[axis] = radius + off
            mask[tuple(idx)] = True
    return mask


def make_weights(shape: str, dim: int, radius: int, seed: int = 0,
                 normalize: bool = True, dtype=np.float32) -> np.ndarray:
    """Dense ``(2r+1)^d`` kernel, uniform in [0.1, 1) on the support and
    zero outside it, scaled to sum to 1 when ``normalize``."""
    rng = np.random.default_rng(seed)
    mask = support_mask(shape, dim, radius)
    w = rng.uniform(0.1, 1.0, size=mask.shape) * mask
    if normalize:
        w = w / w.sum()
    return w.astype(dtype)


def _offsets(radius: int, dim: int):
    rng = range(-radius, radius + 1)
    return list(itertools.product(rng, repeat=dim))


def pad_boundary(x, radius: int, modes):
    """Pad ``radius`` cells per side, axis by axis, with each axis's mode."""
    xp = x
    for ax, m in enumerate(modes):
        pad = [(0, 0)] * x.ndim
        pad[ax] = (radius, radius)
        xp = jnp.pad(xp, pad, mode=PAD_MODE[m])
    return xp


def apply_stencil(x, weights, modes):
    """One update ``y[i] = sum_o w[o] * x[i + o]`` (row-major offsets)."""
    dim = weights.ndim
    if x.ndim != dim:
        raise ValueError(f"grid rank {x.ndim} != kernel rank {dim}")
    radius = (weights.shape[0] - 1) // 2
    w = jnp.asarray(weights, dtype=x.dtype)
    periodic = all(m == "periodic" for m in modes)
    xp = None if periodic else pad_boundary(x, radius, modes)
    y = jnp.zeros_like(x)
    for off in _offsets(radius, dim):
        widx = tuple(o + radius for o in off)
        if periodic:
            shifted = jnp.roll(x, shift=tuple(-o for o in off),
                               axis=tuple(range(dim)))
        else:
            sl = tuple(slice(radius + o, radius + o + n)
                       for o, n in zip(off, x.shape))
            shifted = xp[sl]
        y = y + w[widx] * shifted
    return y


def apply_stencil_steps(x, weights, t: int, modes):
    """``t`` sequential updates (the un-fused ground truth)."""
    def body(carry, _):
        return apply_stencil(carry, weights, modes), None

    y, _ = jax.lax.scan(body, x, None, length=t)
    return y


@functools.partial(jax.jit, static_argnames=("t", "modes", "halo",
                                             "low"))
def _band(xb, yb, weights, t, modes, halo, low):
    """Errors of one band: the program's rows ``yb`` against the oracle
    of the halo-extended input rows ``xb`` (and, for ``low``, the oracle
    computed in bfloat16 put in the program's place)."""
    ref = apply_stencil_steps(xb, weights, t, modes)
    ref = ref[halo:xb.shape[0] - halo]
    if low:
        lo = apply_stencil_steps(xb.astype(jnp.bfloat16),
                                 weights.astype(jnp.bfloat16), t, modes)
        yb = lo[halo:xb.shape[0] - halo].astype(ref.dtype)
    return jnp.max(jnp.abs(yb - ref)), jnp.max(jnp.abs(ref))


def banded_errors(x, y, weights, t: int, modes, band: int, device,
                  low: bool = False):
    """``(max |y - oracle(x)|, max |oracle(x)|)`` over the whole grid,
    ``band`` rows of axis 0 at a time on ``device``.

    ``x`` is the input of one plan call and ``y`` its output; either may
    be sharded over several chips.  Axis 0 must be periodic when it is
    banded (each band's halo rows wrap round the grid).  ``low=True``
    gives the control's reading instead: the oracle in bfloat16 in place
    of ``y``.
    """
    n = x.shape[0]
    radius = (weights.shape[0] - 1) // 2
    halo = t * radius
    band = min(band, n)
    if n % band:
        raise ValueError(f"band {band} does not divide {n} rows")
    if band < n and modes[0] != "periodic":
        raise ValueError("banding needs a periodic axis 0")
    w = jax.device_put(jnp.asarray(weights, x.dtype), device)
    err = top = jnp.zeros((), x.dtype)
    for lo in range(0, n, band):
        if band == n:
            xb = x
            h = 0
        else:
            rows = np.arange(lo - halo, lo + band + halo) % n
            xb = jnp.take(x, jnp.asarray(rows), axis=0)
            h = halo
        xb = jax.device_put(xb, device)
        yb = jax.device_put(y[lo:lo + band], device)
        e, m = _band(xb, yb, w, t, tuple(modes), h, low)
        err, top = jnp.maximum(err, e), jnp.maximum(top, m)
    return float(err), float(top)
