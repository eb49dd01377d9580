"""MXU-path N-D stencil kernel: decompose-to-banded-matmul (the paper's
"Tensor Core" adaptation, re-thought for the TPU systolic array).

2D is the base case below; 3D grids flatten their (z, y) shift pairs into
the same radius-r banded contractions along the last dim
(``build_bands_nd``, DESIGN.md §9) and lower through the halo-plane slab
substrate; 1D grids route through the 2D substrate lifted to (1, N).

Transformation (DESIGN.md §2):
  * decomposition: the (2R+1)^2 kernel splits into 2R+1 row vectors
    (paper §2.2.1 "Decomposing");
  * replication/alignment: each row vector w[dy, :] is materialized as a
    banded (Toeplitz) matrix  B_dy of shape (TILE_N + 2R, TILE_N) with
    B_dy[j+dx, j] = w[dy, dx]  -- this satisfies the MXU operand-size
    constraint (full 128-wide tiles) at the cost of zero padding
    (paper §2.2.2 "sparse redundancy"), with structural sparsity
        S = (2R+1) / (TILE_N + 2R)
    (see perfmodel.sparsity_banded);
  * contraction: out[:, j] += A_dy @ B_dy  where A_dy is the dy-shifted
    (STRIP_M, TILE_N + 2R) slab of the column tile j of the halo-extended
    strip.  Matmuls run in the input dtype with f32 accumulation (MXU
    semantics).

The substrate is the halo-row sub-blocked strip pipeline (kernels.common,
DESIGN.md §3): a 2D (strip, h-block) grid assembles each output strip's
halo-extended rows from (h_block, N) blocks -- (1 + 2*h_block/strip_m)x
HBM reads per step -- with the horizontal halo wrapped in-VMEM.  Widths
exceeding the VMEM
budget column-tile the last axis too (DESIGN.md §10): the contraction
then consumes a CARRIED 2*t*r x-halo instead of re-wrapping, and a
final chunk narrower than ``tile_n`` (awkward/prime widths -- the
choose_tile cap policy) contracts against the banded operand's leading
submatrix, which IS the narrower band.

Two fusion regimes share this kernel (paper §2.2.3 + DESIGN.md §4):

  * monolithic (``t=1`` on composed weights): the wrapper is handed a
    fused kernel of radius R = t*r and runs ONE banded contraction -- no
    intermediate reuse, compute inflated by alpha, exactly the
    monolithic-fusion regime the paper models;
  * intermediate reuse (``t>1`` on base weights): ``t`` radius-r banded
    contractions execute inside one kernel with every intermediate resident
    in VMEM (vertical halo t*r, horizontal wrap re-applied per step).  The
    fused kernel never materializes, so alpha = 1; the price is a
    shrinking-halo recompute factor beta = 1 + r*(t-1)/strip_m
    (perfmodel.halo_recompute_factor) -- the paper's taxonomy implies this
    fifth regime but never implements it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .common import (apply_boundary_fills, choose_tile, extend_columns,
                     lift_boundary_1d, resolve_substrate_geom,
                     slab_substrate_call, strip_substrate_call,
                     validate_tiling)
from repro.stencil.boundary import resolve_boundary


def build_bands(weights: np.ndarray, tile_n: int) -> np.ndarray:
    """(ROWS, TILE_N + 2R, TILE_N) banded weight matrices, one per kernel row.

    ``weights`` is a 2D kernel whose LAST axis carries the x taps (radius
    R from that axis); rows may number 2R+1 (square 2D kernels) or 1 (the
    lifted-1D kernel).
    """
    w = np.asarray(weights)
    rows, kx = w.shape
    radius = (kx - 1) // 2
    bands = np.zeros((rows, tile_n + 2 * radius, tile_n), dtype=w.dtype)
    # Vectorized diagonal fill: tap dx of every row lands on the band
    # (j + dx, j); writing the zero taps too is identical to skipping
    # them, since the destination starts zeroed.
    j = np.arange(tile_n)
    for dx in range(kx):
        bands[:, j + dx, j] = w[:, dx, None]
    return bands


def build_bands_nd(weights: np.ndarray, tile_n: int):
    """Flatten an N-D kernel's leading shift tuples into banded operands.

    Returns ``(offsets, bands)``: ``offsets`` is the host-side list of
    leading-axis shift tuples (e.g. (dz, dy) for 3D) whose x-row
    ``weights[off + (:,)]`` is structurally nonzero, and ``bands`` stacks
    one (TILE_N + 2R, TILE_N) banded matrix per such row.  All-zero rows
    (most of a star stencil's (dz, dy) pairs) are dropped at build time --
    they would contract to exact zeros, so skipping them cuts both the
    banded operand and the per-step MXU work without touching the result.
    """
    w = np.asarray(weights)
    lead = w.shape[:-1]
    offsets = [off for off in np.ndindex(*lead)
               if np.count_nonzero(w[off + (slice(None),)])]
    rows = np.stack([w[off + (slice(None),)] for off in offsets])
    return offsets, build_bands(rows, tile_n)


def band_sparsity(weights: np.ndarray, tile_n: int) -> float:
    """Measured S of the built operands = nonzeros / total (sanity vs model).

    Closed form: each nonzero tap (off, dx) lands on its own diagonal
    (j + dx, j), contributing exactly ``tile_n`` entries with no
    collisions (dx = row - col is unique per element), so over the rows
    ``build_bands_nd`` keeps (all-zero leading rows of a 3D star already
    dropped)

        S = nnz_taps * tile_n / (n_rows * (tile_n + 2r) * tile_n)
          = nnz_taps / (n_rows * (tile_n + 2r)).

    Cross-checked against the materialized operand in tests; identical to
    the historical 2D measurement for 2D kernels, whose rows are never
    all-zero.
    """
    w = np.asarray(weights)
    if w.ndim == 1:
        w = w[None, :]
    radius = (w.shape[-1] - 1) // 2
    per_row = np.count_nonzero(w.reshape(-1, w.shape[-1]), axis=1)
    per_row = per_row[per_row > 0]
    return float(per_row.sum()) / (per_row.size * (tile_n + 2 * radius))


def banded_dot(a: jax.Array, b: jax.Array, compute_dtype) -> jax.Array:
    """One banded MXU contraction, accumulated in f32.  An f32 contraction
    asks for full f32 precision: Mosaic's default for f32 operands is not
    specified to be exact, and the plans are held to the f32 oracle."""
    a, b = a.astype(compute_dtype), b.astype(compute_dtype)
    precision = (jax.lax.Precision.HIGHEST if a.dtype == jnp.float32
                 else None)
    return jax.lax.dot(a, b, precision=precision,
                       preferred_element_type=jnp.float32)


def _banded_step(z: jax.Array, bands_ref, offsets, lead_extents,
                 radius: int, tile_n: int, compute_dtype,
                 wrap_x: bool = True, mode_x: str = "periodic") -> jax.Array:
    """One radius-r banded contraction, any rank.

    ``z``: (..., n) rows; ``offsets`` the host-side leading shift tuples
    matching ``bands_ref`` rows (the flattened (z, y) shift pairs for
    3D, (dy,) singletons for 2D); ``lead_extents`` the kernel's
    leading-axis extents.  Returns the update with every leading axis
    shrunk by its kernel extent - 1, accumulated in f32 across column
    chunks of width ``tile_n``: each (dz, dy) shifted slab is flattened
    to rows and contracted against its banded operand.

    ``wrap_x`` (full-width substrates: rows are complete global rows)
    materializes the x-halo in-VMEM under ``mode_x`` (periodic = the
    historical wrap); ``wrap_x=False`` (the column-tiled substrate,
    DESIGN.md §10) consumes the CARRIED x-halo instead, shrinking the
    last axis by 2*radius.  A final chunk narrower than ``tile_n``
    (widths not divisible by the tile -- the choose_tile cap policy)
    contracts against the leading submatrix of the banded operand,
    which is exactly the narrower band.
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
    bands_w = bands_ref.shape[-1]
    cols = []
    start = 0
    while start < n_out:
        wcur = min(tile_n, n_out - start)
        acc = jnp.zeros((m, wcur), jnp.float32)
        for p, off in enumerate(offsets):
            sl = tuple(slice(off[i], off[i] + lead[i])
                       for i in range(len(lead)))
            a = zw[sl + (slice(start, start + wcur + 2 * radius),)]
            a = a.reshape(m, wcur + 2 * radius)
            b = bands_ref[p]                  # (bands_w + 2r, bands_w)
            if wcur != bands_w:
                b = b[:wcur + 2 * radius, :wcur]
            acc = acc + banded_dot(a, b, compute_dtype)
        cols.append(acc)
        start += wcur
    out = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return out.reshape(lead + (n_out,))


def _banded_steps(cur: jax.Array, edges, bands_ref, offsets, lead_extents,
                  t: int, radius: int, tile_n: int, compute_dtype, modes,
                  wrap_x: bool = True, x_pad: int = 0) -> jax.Array:
    # Non-periodic launches re-impose the boundary on the shrinking
    # out-of-domain halo before every step, exactly like the VPU kernel
    # (DESIGN.md §15).
    for k in range(t):
        if edges is not None:
            cur = apply_boundary_fills(cur, modes, edges, (t - k) * radius,
                                       x_pad=x_pad, x_tiled=not wrap_x)
        cur = _banded_step(cur, bands_ref, offsets, lead_extents, radius,
                           tile_n, compute_dtype, wrap_x, modes[-1])
    return cur


def stencil_matmul(
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
    """``t`` stencil steps via banded MXU contractions, per-axis boundaries.

    ``boundary`` is a per-axis mode spec (DESIGN.md §15; ``None`` = all
    periodic, the historical behavior bit for bit).  ``name`` and
    ``scopes`` are the launch's trace name and in-kernel scopes
    (``common._launch``).

    N-D: 2D and 3D grids contract their flattened leading shift tuples
    against per-row banded operands; 1D grids route through the 2D
    substrate lifted to (1, N).

    ``t=1``: one contraction of ``weights`` -- which may itself be a fused
    kernel of radius t*r (the paper's monolithic kernel fusion).
    ``t>1``: the intermediate-reuse regime -- t radius-r contractions of the
    BASE kernel with intermediates resident in VMEM (``fused_matmul_reuse``
    in repro.kernels.ops).

    ``tile_m`` is the strip height; ``tile_n`` the column-chunk width of
    each contraction (the banded operand is (rows, tile_n + 2r, tile_n);
    widths not divisible by ``tile_n`` contract a narrower final chunk
    against the operand's leading submatrix, so awkward/prime widths
    keep full-size chunks -- the ``choose_tile`` cap policy);
    ``h_block`` the halo sub-block height (``None`` = auto); 3D grids add
    ``z_slab``/``z_block``; 2D/3D grids add ``w_tile``/``w_block`` (the
    column-tiled W substrate, DESIGN.md §10 -- each step then consumes a
    carried x-halo instead of re-wrapping).  Any left ``None`` is
    auto-chosen (``resolve_substrate_geom`` / ``choose_tile``); explicit
    values are validated strictly.
    """
    w = np.asarray(weights)
    if x.ndim != w.ndim:
        raise ValueError(f"grid rank {x.ndim} != kernel rank {w.ndim}")
    if x.ndim == 1:
        # coerce h_block exactly like resolve_substrate_geom's dim-1 rule
        # (see stencil_direct); 1D never column-tiles
        y = stencil_matmul(x[None, :], w[None, :], t=t, tile_m=1,
                           tile_n=tile_n, h_block=h_block and 1, w_tile=0,
                           interpret=interpret, compute_dtype=compute_dtype,
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
    bands = jnp.asarray(bands_np)
    lead_extents = w.shape[:-1]

    def compute(cur, edges, bands_ref):
        return _banded_steps(cur, edges, bands_ref, offsets, lead_extents,
                             t, radius, tile_n, compute_dtype, modes,
                             wrap_x=not geom.w_tile, x_pad=x_pad)

    if x.ndim == 3:
        return slab_substrate_call(compute, x, geom, halo, interpret,
                                   consts=(bands,),
                                   x_halo=x_halo if geom.w_tile else 0,
                                   boundary=modes, name=name, scopes=scopes)
    return strip_substrate_call(compute, x, geom.strip_m, geom.h_block,
                                halo, interpret, consts=(bands,),
                                w_tile=geom.w_tile, w_block=geom.w_block,
                                x_halo=x_halo if geom.w_tile else 0,
                                boundary=modes, name=name, scopes=scopes)
