"""Sparse-compacted MXU stencil kernel: the Sparse-Tensor-Core regime.

The banded operands ``build_bands_nd`` emits for star stencils are mostly
structural zeros: a single-tap row (e.g. the (dz=0, dy=+1) row of a 3D
star) still materializes a (tile_n + 2r, tile_n) band of which only
``tile_n`` rows along the contraction axis carry data.  The paper's
sequels (SPIDER, SparStencil -- PAPERS.md) show that structured
compaction of exactly this pattern is how Sparse Tensor Cores widen the
MXU sweet spot.  This module executes that regime (DESIGN.md §14):

  * **host-side compaction** (:func:`compact_bands`): each band keeps
    only the contiguous hull of its structurally-nonzero contraction
    rows.  For a row whose taps span [dx_min, dx_max] the nonzero band
    rows are the union of [dx, dx + tile_n) over its taps -- contiguous
    because tile_n >= the tap span -- i.e. exactly
    [dx_min, dx_max + tile_n): ``tile_n + span`` rows instead of
    ``tile_n + 2r``, span = dx_max - dx_min in [0, 2r].  The kept rows
    of every band are stacked into ONE packed operand (a single launch
    const), shrinking VMEM residency by the kept-row fraction S;
  * **in-kernel gather** (:func:`_sparse_banded_step`): the matching
    input rows are gathered by slicing the shifted slab at offset
    ``lo = dx_min`` with width ``wcur + span`` -- the per-step MXU
    K-dimension shrinks by the same S (2:4-style structured compaction
    generalized to the band pattern: the "metadata" is the per-band
    (lo, span, row_start) triple, static on the host);
  * **bitwise equality**: the dropped band rows are exact zeros, and an
    additive identity never changes a float sum regardless of where the
    reduction tree absorbs it, so the compacted contraction is
    bit-for-bit equal to the dense ``stencil_matmul`` path (asserted in
    tests and in the benchmark sweep).

Box kernels compact to span = 2r on every row (S = 1): the backend still
builds and runs -- identically to the dense path -- so the guard ladder
can route through it unconditionally; it just never wins on price.

Both fusion regimes of stencil_matmul are mirrored: ``t=1`` on composed
weights (monolithic) and ``t>1`` with VMEM-resident intermediates
(``fused_sparse_matmul``, the reuse regime).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import (apply_boundary_fills, choose_tile, extend_columns,
                     lift_boundary_1d, resolve_substrate_geom,
                     slab_substrate_call, strip_substrate_call,
                     validate_tiling)
from .stencil_matmul import banded_dot, build_bands_nd
from repro.stencil.boundary import resolve_boundary


def compact_bands(offsets, bands: np.ndarray):
    """Compact banded operands to their structurally-nonzero band rows.

    ``offsets``/``bands`` as returned by ``build_bands_nd``: one
    (tile_n + 2r, tile_n) band per surviving leading-shift tuple.
    Returns ``(row_index, packed_bands)``:

      * ``row_index``: per band, the np.arange of kept contraction-row
        indices -- the contiguous hull [dx_min, dx_max + tile_n) of the
        nonzero rows (a superset hull is always safe: a kept all-zero
        row contracts to exact zeros);
      * ``packed_bands``: the kept rows of all bands stacked along axis
        0 into one (sum_p(tile_n + span_p), tile_n) array -- a single
        VMEM-resident launch const whose row count over
        n_offsets * (tile_n + 2r) IS the kept-row fraction S.
    """
    bands = np.asarray(bands)
    if len(offsets) != bands.shape[0]:
        raise ValueError(f"{len(offsets)} offsets != {bands.shape[0]} bands")
    row_index = []
    packed = []
    for p in range(bands.shape[0]):
        nz = np.flatnonzero(np.any(bands[p] != 0, axis=1))
        if nz.size == 0:
            raise ValueError(f"band {p} is all-zero (offset {offsets[p]}); "
                             "build_bands_nd should have dropped it")
        lo, hi = int(nz[0]), int(nz[-1]) + 1
        row_index.append(np.arange(lo, hi))
        packed.append(bands[p, lo:hi])
    return tuple(row_index), np.concatenate(packed, axis=0)


def band_row_meta(row_index, tile_n: int):
    """Static gather metadata from ``compact_bands`` row indices.

    Per band: ``(lo, span, row_start)`` -- the input-gather offset, the
    tap span (kept rows = tile_n + span), and the band's first row in
    the packed operand.
    """
    meta = []
    start = 0
    for idx in row_index:
        lo = int(idx[0])
        span = int(idx.size) - tile_n
        if span < 0:
            raise ValueError(f"band keeps {idx.size} rows < tile_n {tile_n}")
        meta.append((lo, span, start))
        start += int(idx.size)
    return tuple(meta)


def kept_row_fraction(weights, tile_n: int) -> float:
    """Kept-row fraction S of the compacted operand (<= 1; 1 for box).

    S = sum_p(tile_n + span_p) / (n_offsets * (tile_n + 2r)): the factor
    by which compaction shrinks both the VMEM-resident operand and the
    per-step MXU K-dimension.  This is the *achievable* row-structured
    sparsity -- ``band_sparsity`` measures element nonzeros, which row
    compaction cannot fully reach (a multi-tap band row keeps its
    in-row zeros).
    """
    w = np.asarray(weights, dtype=np.float32)
    if w.ndim == 1:
        w = w[None, :]
    offsets, bands = build_bands_nd(w, tile_n)
    row_index, packed = compact_bands(offsets, bands)
    radius = (bands.shape[1] - bands.shape[2]) // 2
    return packed.shape[0] / (len(offsets) * (tile_n + 2 * radius))


def _sparse_banded_step(z: jax.Array, packed_ref, offsets, row_meta,
                        lead_extents, radius: int, tile_n: int,
                        compute_dtype, wrap_x: bool = True,
                        mode_x: str = "periodic") -> jax.Array:
    """One radius-r compacted banded contraction, any rank.

    Mirrors ``stencil_matmul._banded_step`` exactly, except each offset
    contracts only its kept band rows: the input slab is gathered at
    ``lo_p`` with width ``wcur + span_p`` and multiplied against the
    band's rows of the packed operand.  A final chunk narrower than
    ``tile_n`` re-expands to the DENSE band prefix (the kept rows for
    width wcur are [lo, lo + wcur + span) -- zero-padded back to
    [0, wcur + 2r)): XLA's small-dot rewrites reassociate degenerate
    reductions, so keeping the remainder chunk graph-identical to the
    dense path is what preserves bitwise equality; the compaction win
    comes from the full-width chunks, which dominate.
    """
    if wrap_x:
        zw = extend_columns(z, radius, mode_x)         # (..., n + 2r)
        n_out = z.shape[-1]
    else:
        zw = z                                         # halo carried
        n_out = z.shape[-1] - 2 * radius
    lead = tuple(z.shape[i] - (lead_extents[i] - 1)
                 for i in range(len(lead_extents)))
    m = 1
    for d in lead:
        m *= d
    bands_w = packed_ref.shape[-1]
    cols = []
    start = 0
    while start < n_out:
        wcur = min(tile_n, n_out - start)
        acc = jnp.zeros((m, wcur), jnp.float32)
        for p, off in enumerate(offsets):
            lo, span, rs = row_meta[p]
            sl = tuple(slice(off[i], off[i] + lead[i])
                       for i in range(len(lead)))
            if wcur == bands_w:
                a = zw[sl + (slice(start + lo, start + lo + wcur + span),)]
                a = a.reshape(m, wcur + span)
                b = packed_ref[rs:rs + wcur + span]   # compacted rows
            else:
                # remainder chunk: dense-shaped contraction (see docstring)
                a = zw[sl + (slice(start, start + wcur + 2 * radius),)]
                a = a.reshape(m, wcur + 2 * radius)
                kept = packed_ref[rs:rs + wcur + span, :wcur]
                b = jnp.pad(kept, ((lo, 2 * radius - span - lo), (0, 0)))
            acc = acc + banded_dot(a, b, compute_dtype)
        cols.append(acc)
        start += wcur
    out = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return out.reshape(lead + (n_out,))


def _sparse_banded_steps(cur: jax.Array, edges, packed_ref, offsets,
                         row_meta, lead_extents, t: int, radius: int,
                         tile_n: int, compute_dtype, modes,
                         wrap_x: bool = True, x_pad: int = 0) -> jax.Array:
    # Non-periodic launches re-impose the boundary on the shrinking
    # out-of-domain halo before every step, exactly like the dense
    # kernels (DESIGN.md §15).
    for k in range(t):
        if edges is not None:
            cur = apply_boundary_fills(cur, modes, edges, (t - k) * radius,
                                       x_pad=x_pad, x_tiled=not wrap_x)
        cur = _sparse_banded_step(cur, packed_ref, offsets, row_meta,
                                  lead_extents, radius, tile_n,
                                  compute_dtype, wrap_x, modes[-1])
    return cur


def stencil_sparse_matmul(
    x: jax.Array,
    weights,
    t: int = 1,
    tile_m: int = None,
    tile_n: int = None,
    h_block: int = None,
    z_slab: int = None,
    z_block: int = None,
    w_tile: int = None,
    w_block: int = None,
    interpret: bool = False,
    compute_dtype=None,
    boundary=None,
    name: str = None,
    scopes: bool = False,
) -> jax.Array:
    """``t`` stencil steps via sparse-compacted MXU contractions.

    Drop-in, bitwise-equal replacement for ``stencil_matmul`` that
    contracts each banded operand over only its structurally-nonzero
    band rows (kept-row fraction S = ``kept_row_fraction``).  Same
    fusion regimes: ``t=1`` monolithic on (possibly fused) weights,
    ``t>1`` intermediate reuse with VMEM-resident steps
    (``fused_sparse_matmul`` in the registry).  All substrate/tiling
    parameters behave exactly as in ``stencil_matmul``.
    """
    w = np.asarray(weights)
    if x.ndim != w.ndim:
        raise ValueError(f"grid rank {x.ndim} != kernel rank {w.ndim}")
    if x.ndim == 1:
        # coerce h_block like the other kernels' 1D lift
        y = stencil_sparse_matmul(x[None, :], w[None, :], t=t, tile_m=1,
                                  tile_n=tile_n, h_block=h_block and 1,
                                  w_tile=0,
                                  interpret=interpret,
                                  compute_dtype=compute_dtype,
                                  boundary=lift_boundary_1d(boundary),
                                  name=name, scopes=scopes)
        return y[0]

    modes = resolve_boundary(boundary, x.ndim)
    radius = (w.shape[-1] - 1) // 2
    halo = t * ((w.shape[0] - 1) // 2)        # 0 for the lifted-1D kernel
    wid = x.shape[-1]
    x_halo = t * radius                       # carried if column-tiled
    geom = resolve_substrate_geom(x.shape, halo, x.dtype.itemsize,
                                  tile_m, h_block, z_slab, z_block,
                                  w_tile, w_block, x_halo)
    tile_n = choose_tile(wid) if tile_n is None else min(tile_n, wid)
    validate_tiling(x.shape, geom.strip_m, tile_n, halo, radius,
                    geom.h_block, geom.z_slab if x.ndim == 3 else None,
                    geom.z_block, geom.w_tile, geom.w_block, x_halo,
                    boundary=modes)
    if compute_dtype is None:
        compute_dtype = x.dtype
    x_pad = (-wid) % geom.w_tile if geom.w_tile else 0  # remainder path

    offsets, bands_np = build_bands_nd(w.astype(np.float32), tile_n)
    row_index, packed_np = compact_bands(offsets, bands_np)
    row_meta = band_row_meta(row_index, tile_n)
    packed = jnp.asarray(packed_np)
    lead_extents = w.shape[:-1]

    def compute(cur, edges, packed_ref):
        return _sparse_banded_steps(cur, edges, packed_ref, offsets,
                                    row_meta, lead_extents, t, radius,
                                    tile_n, compute_dtype, modes,
                                    wrap_x=not geom.w_tile, x_pad=x_pad)

    if x.ndim == 3:
        return slab_substrate_call(compute, x, geom, halo, interpret,
                                   consts=(packed,),
                                   x_halo=x_halo if geom.w_tile else 0,
                                   boundary=modes, name=name, scopes=scopes)
    return strip_substrate_call(compute, x, geom.strip_m, geom.h_block,
                                halo, interpret, consts=(packed,),
                                w_tile=geom.w_tile, w_block=geom.w_block,
                                x_halo=x_halo if geom.w_tile else 0,
                                boundary=modes, name=name, scopes=scopes)
