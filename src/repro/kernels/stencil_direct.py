"""VPU-path N-D stencil kernel (the "CUDA core" baseline of the paper).

One output cell is a (STRIP_M, N) band (2D) or a (Z_SLAB, STRIP_M, N)
slab-strip (3D), lowered through the shared substrate launchers
(``common.strip_substrate_call`` / ``common.slab_substrate_call``).  On
the sub-blocked substrate (default, DESIGN.md §3/§9) the Pallas grid
walks halo blocks: each grid cell copies one input block into a VMEM
scratch -- the cell's own blocks plus the single ring of neighbor blocks
that can contain halo planes/rows -- and the final cell computes on the
assembled halo-extended region, so HBM reads per step are
(1 + 2*h_block/strip_m) x the grid in 2D and additionally
(1 + 2*z_block/z_slab) x in 3D.  The stencil is an unrolled sum of shifted
slices times scalar taps -- pure element-wise VPU work, accumulated in
f32.  1D grids route through the 2D substrate lifted to (1, N): the
vertical halo is zero, so strips stream only their own rows.

Column operands (``column_shift_form``): where the last axis is periodic
on a full-width substrate and some off-centre column offset feeds two or
more taps (box stencils), each offset's operand is ONE lane rotate
(``pltpu.roll``) of the region over its own width, shared by that
offset's taps -- 2r rotates per step.  Otherwise (star stencils, the
lifted 1D kernel, non-periodic last axes, column tiling) the last-axis
halo is materialized by ``extend_columns`` and each tap slices it at its
lane offset.  Both forms sum the same values in the same tap order, so
their outputs are bit-for-bit equal.

Supports an in-kernel temporal-fusion depth ``t`` (the paper's CUDA-core
temporal fusion, §3.2.2): ``t`` sequential updates on leading-axis halos
of ``t*r``, intermediates living entirely in VMEM => per-point HBM traffic
stays flat while compute scales by t (I = t*K/D).  Because every row of
the extended region is a true global row, the last-axis wrap is re-applied
per step at radius ``r`` -- no 2*t*r horizontal halo is ever carried.
This kernel IS `stencil_fused`'s engine; ``t=1`` is the plain baseline.

Grids whose FULL-WIDTH working set exceeds the VMEM budget execute on
the column-tiled substrate (DESIGN.md §10): the grid gains a
(w_tile, w_block) dimension, the x-halo is assembled from neighbor
column blocks instead of the in-VMEM wrap, and the tap-sum CARRIES a
2*t*r-wide x support that shrinks per step (``wrap_x=False``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas import tpu as pltpu

from .common import (apply_boundary_fills, extend_columns, lift_boundary_1d,
                     resolve_substrate_geom, slab_substrate_call,
                     strip_substrate_call, validate_tiling, wrap_columns)
from repro.stencil.boundary import resolve_boundary


def column_shift_form(weights, mode_x: str = "periodic",
                      wrap_x: bool = True) -> str:
    """How ``_stencil_steps`` forms its column operands: ``"roll"`` or
    ``"slice"``, decided from what the launch sees.

    ``roll`` needs complete periodic rows (``wrap_x`` with a periodic
    last axis) and an off-centre column offset read by two or more
    nonzero taps, so one rotate serves several taps: every box stencil.
    Star stencils and the lifted 1D kernel read each off-centre offset
    once, non-periodic modes synthesize fill columns, and column-tiled
    rows are partial: those ``slice`` the extended columns.
    """
    w = np.asarray(weights)
    if not wrap_x or mode_x != "periodic":
        return "slice"
    r = (w.shape[-1] - 1) // 2
    taps = np.count_nonzero(w.reshape(-1, w.shape[-1]), axis=0)
    return "roll" if (np.delete(taps, r) >= 2).any() else "slice"


def _rolled_columns(cur: jax.Array, weights, lead,
                    interpret: bool = False) -> dict:
    """Column offset ``j`` -> (operand, leading-axis origin).

    The operand is ``cur`` restricted to the union of the leading-axis
    windows that ``j``'s nonzero taps read (all of ``cur`` for a box),
    rotated over the region's own width ``n`` so that lane ``c`` holds
    the periodic column ``c + j - r``: exactly those rows of
    ``wrap_columns(cur, r)[..., j:j + n]``.  The centre offset is not
    rotated.  Interpret mode takes that slice itself: the interpreter
    runs on XLA:CPU, whose FMA contraction of the tap sum follows the
    layout of the buffers it reads, so the same values from another
    layout can differ in the last ulp from the oracle, and from the
    slice form.
    """
    r, n = (weights.shape[-1] - 1) // 2, cur.shape[-1]
    operands = {}
    for j in range(weights.shape[-1]):
        rows = np.argwhere(weights[..., j] != 0)
        if not len(rows):
            continue
        lo = tuple(int(a) for a in rows.min(axis=0))
        win = cur[tuple(slice(a, b + m) for a, b, m
                        in zip(lo, rows.max(axis=0), lead))]
        if j != r:
            win = (wrap_columns(win, r)[..., j:j + n] if interpret else
                   pltpu.roll(win, (r - j) % n, axis=cur.ndim - 1))
        operands[j] = (win, lo)
    return operands


def _stencil_steps(cur: jax.Array, edges, weights, t: int, radius: int,
                   modes, wrap_x: bool = True, x_pad: int = 0,
                   interpret: bool = False) -> jax.Array:
    """``t`` unrolled tap-sum updates on a halo-extended f32 region.

    N-D: ``weights`` has ``cur.ndim`` axes; each step consumes the
    per-axis kernel extent on every leading axis.  ``wrap_x`` (the
    full-width substrates, where every row is a complete global row)
    re-extends the last axis at ``radius`` per step under its boundary
    mode -- by lane rotates where ``column_shift_form`` says ``roll``,
    else by ``extend_columns`` and a lane slice per tap; ``wrap_x=False``
    (the column-tiled substrate, DESIGN.md §10 -- rows are partial, no
    re-extension is possible) instead CONSUMES the carried x-halo like a
    leading axis, shrinking the last dim by 2*radius per step.

    Non-periodic launches (``edges`` is not None) re-impose every
    non-periodic axis's boundary values on the current out-of-domain
    halo depth BEFORE each step -- the depth shrinks with the region,
    ``(t-k)*radius`` at step ``k`` -- matching the oracle, which
    re-pads the *updated* field every step (DESIGN.md §15); ``x_pad``
    is the remainder path's right-padding column count, shifting the
    last tile's x fill (the pad tail only feeds sliced-off columns).
    """
    wshape = weights.shape
    roll = column_shift_form(weights, modes[-1], wrap_x) == "roll"
    for k in range(t):
        if edges is not None:
            cur = apply_boundary_fills(cur, modes, edges, (t - k) * radius,
                                       x_pad=x_pad, x_tiled=not wrap_x)
        lead = tuple(cur.shape[i] - (wshape[i] - 1)
                     for i in range(cur.ndim - 1))
        if roll:
            n = cur.shape[-1]
            operands = _rolled_columns(cur, weights, lead, interpret)
        elif wrap_x:
            z = extend_columns(cur, radius, modes[-1])  # (..., n + 2r)
            n = cur.shape[-1]
        else:
            z = cur                           # halo carried in the region
            n = cur.shape[-1] - 2 * radius
        acc = jnp.zeros(lead + (n,), jnp.float32)
        for idx in np.ndindex(*wshape):
            w = float(weights[idx])
            if w == 0.0:   # star stencils: skip zero taps at trace time
                continue
            if roll:
                src, lo = operands[idx[-1]]
                sl = tuple(slice(idx[i] - lo[i], idx[i] - lo[i] + lead[i])
                           for i in range(len(lead)))
            else:
                src = z
                sl = tuple(slice(idx[i], idx[i] + lead[i])
                           for i in range(len(lead)))
                sl += (slice(idx[-1], idx[-1] + n),)
            acc = acc + w * src[sl]
        cur = acc
    return cur


def stencil_direct(
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
    boundary=None,
    name: str = None,
    scopes: bool = False,
) -> jax.Array:
    """``t`` fused time steps of an N-D stencil, per-axis boundaries.

    ``boundary`` is a per-axis mode spec (DESIGN.md §15: ``periodic`` |
    ``zero`` | ``reflect`` | ``replicate``; ``None`` = all periodic,
    the historical behavior bit for bit).  ``name`` and ``scopes`` are
    the launch's trace name and in-kernel scopes (``common._launch``).
    ``weights``: host-side (2r+1)^d ndarray (zeros outside support); the
    grid rank must match ``weights.ndim`` (1, 2 or 3).  ``tile_m`` is the
    strip height and ``h_block`` the halo sub-block height; 3D grids add
    ``z_slab`` (slab depth) and ``z_block`` (halo-plane block depth);
    2D/3D grids add ``w_tile``/``w_block`` (the column-tiled W substrate,
    DESIGN.md §10: ``w_tile=0`` pins full width, ``None`` auto-tiles only
    when full width exceeds the VMEM budget) -- any left ``None``
    (default) is auto-sized via ``resolve_substrate_geom`` (divisors,
    halo-covering, VMEM-budgeted); explicit values are validated
    strictly (``h_block=tile_m`` reads whole neighbor strips).  ``tile_n``
    is accepted for
    signature parity with the MXU kernel but unused (the VPU path's only
    column tiling is the substrate's own).
    """
    del tile_n  # the VPU compute never chunks columns
    w = np.asarray(weights)
    if x.ndim != w.ndim:
        raise ValueError(f"grid rank {x.ndim} != kernel rank {w.ndim}")
    if x.ndim == 1:
        # The lifted (1, N) grid's only h_block is 1 and it never
        # column-tiles: a positive pin coerces to 1 like
        # resolve_substrate_geom's dim-1 rule (0 is refused there too),
        # so kernel-level and plan-level pins can never disagree.  The
        # synthetic row axis is periodic (it has no halo); the real axis
        # keeps its mode.
        y = stencil_direct(x[None, :], w[None, :], t=t, tile_m=1,
                           h_block=h_block and 1, w_tile=0,
                           interpret=interpret,
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
    validate_tiling(x.shape, geom.strip_m, wid, halo, radius, geom.h_block,
                    geom.z_slab if x.ndim == 3 else None, geom.z_block,
                    geom.w_tile, geom.w_block, x_halo, boundary=modes)
    x_pad = (-wid) % geom.w_tile if geom.w_tile else 0  # remainder path

    def compute(cur, edges):
        return _stencil_steps(cur, edges, w, t, radius, modes,
                              wrap_x=not geom.w_tile, x_pad=x_pad,
                              interpret=interpret)

    if x.ndim == 3:
        return slab_substrate_call(compute, x, geom, halo, interpret,
                                   x_halo=x_halo if geom.w_tile else 0,
                                   boundary=modes, name=name, scopes=scopes)
    return strip_substrate_call(compute, x, geom.strip_m, geom.h_block,
                                halo, interpret, w_tile=geom.w_tile,
                                w_block=geom.w_block,
                                x_halo=x_halo if geom.w_tile else 0,
                                boundary=modes, name=name, scopes=scopes)
