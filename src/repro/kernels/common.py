"""Shared Pallas plumbing: the block-ring halo substrate every kernel runs.

TPU Pallas BlockSpecs address non-overlapping blocks (element offset = block
index * block shape), so halo reads cannot be expressed as one overlapping
block.  The substrate instead walks a RING of blocks per output cell
(DESIGN.md §3):

  * the grid is 2D over (strip, h-block): block height ``h_block`` divides
    ``strip_m`` (``nb = strip_m / h_block`` blocks per strip);
  * ONE input reference of block shape (h_block, N) with index map
    ``(i*nb + j - 1) mod (H/h_block)`` walks, for output strip i, the
    top neighbor's LAST h-block (j=0), the strip's own nb blocks
    (j=1..nb), and the bottom neighbor's FIRST h-block (j=nb+1) -- the
    only neighbor rows that can contain halo rows (h_block >= halo);
  * each block is copied into a VMEM scratch of (strip_m + 2*h_block, N);
    on the final j the kernel computes on the assembled halo-extended
    strip and writes the output strip (``pl.when``), so reads per strip
    are ``strip_m + 2*h_block`` rows:

        reads/step = (1 + 2*h_block/strip_m) * H*W*D

    The modulo index map keeps periodic top/bottom boundaries for free,
    and every scratch row is a TRUE global row, so the horizontal wrap
    re-applies to in-VMEM intermediates at every fused step -- the
    property that enables ``fused_matmul_reuse`` (DESIGN.md §4).

``h_block = strip_m`` is the ring's coarsest pin: three full strips per
output strip, read amplification 3 (the whole-strip read pattern).
``h_block`` must be at least 1: 0 names no ring and is refused at
resolution (``check_block_pins``).

N-D HALO-PLANE GENERALIZATION (DESIGN.md §9).  The scheme above is the
d=2 instance of a general halo-plane substrate:

  * 3D grids (Z, H, W) run on a (z-slab, strip, block) Pallas grid: each
    output cell is a (z_slab, strip_m, W) slab-strip, assembled from ONE
    input reference of block shape (z_block, h_block, W) whose index map
    walks the cell's own (z_slab/z_block)x(strip_m/h_block) blocks plus
    the single ring of neighbor blocks that can contain halo planes/rows
    (z_block >= halo, h_block >= halo), into a VMEM scratch of
    (z_slab + 2*z_block, strip_m + 2*h_block, W).  Reads per step:

        (1 + 2*h_block/strip_m) * (1 + 2*z_block/z_slab) * Z*H*W*D

    The last axis keeps the free in-VMEM periodic wrap (every scratch row
    is a TRUE global row), so the fused regimes carry over unchanged.
    ``z_block = z_slab`` with ``h_block = strip_m`` walks 3x3 full
    neighbor slabs, read amplification 9 (the whole-slab read pattern).
  * 1D grids route through the 2D substrate lifted to (1, N): the
    vertical halo is 0, so each strip streams only its own rows
    (the "flat" launch: no ring, read amplification exactly 1) and the
    x-wrap stays in-VMEM.

COLUMN-TILED W AXIS (DESIGN.md §10).  The rings above span the FULL
width in VMEM, so grids with W >> VMEM (weather/fluid planes with W in
the tens of thousands) cannot execute at all.  When the full-width
working set exceeds the VMEM budget the substrate column-tiles the last
axis too:

  * the grid gains a (w-tile, w-block) dimension: each output cell is a
    (strip_m, w_tile) tile (2D) or (z_slab, strip_m, w_tile) cell (3D),
    and the single input reference shrinks to (h_block, w_block) /
    (z_block, h_block, w_block), walking the FULL block ring -- own
    blocks plus every neighbor block that can contain halo rows OR halo
    columns -- into a VMEM scratch of
    (strip_m + 2*h_block, w_tile + 2*w_block) (plus the z axis in 3D);
  * the periodic x-halo is assembled from neighbor COLUMN blocks
    (modulo wrap in the index map, exactly like the vertical axes)
    instead of the in-VMEM ``wrap_columns`` concat -- scratch rows are
    no longer complete global rows, so fused execution must CARRY a
    2*t*r-wide x-halo (``w_block >= t*r``) and shrink it per step, the
    same discipline the leading axes always had.  Reads per step become
    the three-factor product

        (1 + 2*h_block/strip_m)(1 + 2*z_block/z_slab)
            (1 + 2*w_block/w_tile) * Z*H*W*D

  * widths with no usable divisor (primes, awkward W) run through an
    edge-tile remainder path: the input is periodically extended by one
    w_block per side on the host, the column walk drops its modulo wrap
    (the extension carries it), and the padded output columns are
    sliced off -- so ANY width executes at a non-degenerate tile.

``w_tile=0`` is the full-width fast path: the launchers and sizing are
bit-for-bit the pre-column-tiling scheme, and auto-resolution only
column-tiles when full width cannot fit the budget.

``SubstrateGeom`` carries the resolved (z_slab, z_block, strip_m,
h_block, w_tile, w_block) geometry through plans, the selector and the
cache keys; ``resolve_substrate_geom`` is THE shared sizing rule for
every rank.

PER-AXIS BOUNDARIES (DESIGN.md §15).  Every wrap above is the
``periodic`` instance of a per-axis :mod:`repro.stencil.boundary` spec
(``periodic | zero | reflect | replicate``).  Non-periodic axes change
exactly two things, keeping the HBM traffic model (and therefore every
``repro.audit`` block check) bit-identical to periodic:

  * the index maps REFLECT out-of-range block indices at block
    granularity instead of wrapping (``_reflect_block``: -1 -> 1,
    total -> total-2) -- every fetch stays in bounds, no two
    consecutive ring steps fetch the same block, and the fetch count
    per cell is unchanged, so reads/step keep the three-factor product;
  * the halo content those edge fetches assemble is garbage *for the
    mode*, so the kernels re-impose the boundary IN KERNEL before every
    fused step (``apply_boundary_fills`` / ``extend_columns``): the
    out-of-domain depth at step s is (t-s+1)*r, and zero / replicate /
    reflect values are rebuilt from in-domain rows with free ops only
    (slice/flip/broadcast/select -- the jaxpr FLOP audit counts zero
    extra FLOPs).  Re-imposing per step, not once, is what matches the
    oracle, which re-pads every step.

``_launch`` passes the kernels a per-region-axis ``edges`` tuple of
(is_lo, is_hi) grid-edge flags (from ``pl.program_id``) so the fills
fire only on domain-edge cells.  All-periodic specs skip both changes
entirely -- default plans lower through the historical jaxpr bit for
bit.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import trace
from repro.stencil.boundary import PAD_MODE, resolve_boundary

#: Default VMEM working-set budget for strip sizing (bytes).  A kernel
#: compiled for a TPU v5e gets a 16 MiB scoped VMEM limit by default (the
#: compiler's own overflow message states it; the core has 128 MiB); this
#: budget is deliberately HALF of that default (8 MiB) so the other half
#: stays free for Mosaic's double buffering and pipeline slack.  Compiled
#: kernels request ``VMEM_LIMIT_BYTES`` instead of the default, because
#: the budget does not price the tap-sum intermediates (below).
#: Override per process with the REPRO_VMEM_BUDGET
#: environment variable (``vmem_budget_bytes``), validated like
#: REPRO_PLAN_CACHE_SIZE.
VMEM_BUDGET_BYTES = 8 * 1024 * 1024

#: Scoped VMEM limit the compiled kernels request.  The budget above
#: prices the blocks and one copy of the compute region, but Mosaic also
#: keeps every tap-sum intermediate in VMEM: compiled for a TPU v5e
#: (whose default scoped limit is 16 MiB), the 10240-wide VPU kernels
#: sized at the 8 MiB budget allocate 20-35 MiB and are refused.  The
#: v5e core has 128 MiB of VMEM, so half of it leaves those a 2x margin.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024

#: HBM bytes that take as long to read as one grid step of a block-ring
#: launch costs on a TPU v5e: the 3D sizing (``choose_slab_blocks``)
#: prices each step of a call's ring walk at this many bytes of ring
#: reads.  Fitted on the chip over nine pinned Star-3D1R 1024^3 f32, t=2
#: geometries: the kernel time outside the fire step (DMA waits, grid
#: steps and ring-slot stores) read 0.254 us per step plus ring bytes at
#: 867 GB/s (DESIGN.md §9).
STEP_BYTES = 220_000

#: Mosaic's block tiling rule: the last two dims of every BlockSpec block
#: must be multiples of (sublane tile, LANE) or span the whole array dim.
#: The sublane tile is 8 rows of 32-bit words, so packed dtypes need
#: proportionally more rows (``sublane_tile``).
SUBLANE = 8
LANE = 128


def sublane_tile(dtype_bytes: int) -> int:
    """Rows per native (sublane, lane) tile: 8 for 32-bit, 16 for 16-bit,
    32 for 8-bit dtypes."""
    return max(SUBLANE, 32 // dtype_bytes)


def check_tpu_tiling(grid_shape, geom: "SubstrateGeom",
                     dtype_bytes: int) -> None:
    """Raise ``ValueError`` when ``geom`` would launch a block Mosaic
    refuses: a second-minor block (``strip_m``, ``h_block``) that is
    neither a multiple of the sublane tile nor the grid's row count, or a
    lane-axis block (``w_tile``, ``w_block``) that is not a multiple of
    128.  Auto sizing never produces one; only explicit pins can, and
    interpret mode accepts them, so plans check only when compiling."""
    if geom.dim == 1:
        return          # the lifted (1, N) blocks span the whole array
    rows, sub = grid_shape[-2], sublane_tile(dtype_bytes)
    bad = [f"{name}={v} (needs a multiple of {sub} or {rows})"
           for name, v in (("strip_m", geom.strip_m),
                           ("h_block", geom.h_block))
           if v and v % sub and v != rows]
    if geom.w_tile:
        bad += [f"{name}={v} (needs a multiple of {LANE})"
                for name, v in (("w_tile", geom.w_tile),
                                ("w_block", geom.w_block)) if v % LANE]
    if bad:
        raise ValueError(
            f"geometry for grid {tuple(grid_shape)} breaks the TPU block "
            f"tiling rule: {', '.join(bad)}; drop the pins to auto-size, "
            "or pass interpret=True")


def vmem_budget_bytes() -> int:
    """The effective VMEM sizing budget: ``REPRO_VMEM_BUDGET`` if set
    (must be a positive integer number of bytes), else
    :data:`VMEM_BUDGET_BYTES`.  Read at every geometry resolution, so
    tests and long-running servers can retune without reimporting; the
    plan cache folds the effective value into its keys."""
    from repro.core.envutil import env_int
    return env_int("REPRO_VMEM_BUDGET", VMEM_BUDGET_BYTES, minimum=1)


def wrap_columns(x: jax.Array, halo: int) -> jax.Array:
    """Materialize the periodic last-axis halo in-VMEM: (..., n) -> (..., n+2h).

    Valid whenever every row of ``x`` is a complete global row -- true for
    strips, for assembled sub-block scratch rows (2D and 3D), and for all
    intermediates derived from them, which is what lets fused kernels
    re-wrap at every step instead of carrying a 2*t*r-wide horizontal halo.
    """
    h = halo
    return jnp.concatenate([x[..., -h:], x, x[..., :h]], axis=-1)


def extend_columns(x: jax.Array, halo: int, mode: str = "periodic",
                   lo_edge=True, hi_edge=True) -> jax.Array:
    """Mode-aware last-axis halo materialization: (..., n) -> (..., n+2h).

    The boundary generalization of :func:`wrap_columns` for full-width
    kernels (every row is a complete global row, so the domain edge IS
    the array edge).  ``periodic`` is exactly ``wrap_columns``; the other
    modes synthesize the out-of-domain columns from in-domain ones with
    free ops only (concat / flip / broadcast -- zero counted FLOPs).
    Called per fused step, which is what matches the per-step re-padding
    oracle.  ``lo_edge``/``hi_edge`` (static or traced bools) select the
    boundary fill vs the true wrap halo -- full-width kernels own both
    edges, so the defaults apply; the distributed stepper passes shard
    masks.
    """
    if mode == "periodic":
        return wrap_columns(x, halo)
    h = halo
    wrap_lo, wrap_hi = x[..., -h:], x[..., :h]
    if mode == "zero":
        lo = hi = jnp.zeros_like(wrap_lo)
    elif mode == "replicate":
        reps = (1,) * (x.ndim - 1) + (h,)
        lo = jnp.tile(x[..., :1], reps)
        hi = jnp.tile(x[..., -1:], reps)
    elif mode == "reflect":
        n = x.shape[-1]
        lo = _mirror(x, 1, h + 1, x.ndim - 1)
        hi = _mirror(x, n - h - 1, n - 1, x.ndim - 1)
    else:
        raise ValueError(f"unknown boundary mode {mode!r}")
    if lo_edge is not True:
        lo = jnp.where(lo_edge, lo, wrap_lo)
    if hi_edge is not True:
        hi = jnp.where(hi_edge, hi, wrap_hi)
    return jnp.concatenate([lo, x, hi], axis=-1)


def _mirror(x: jax.Array, lo: int, hi: int, axis: int) -> jax.Array:
    """``x[lo:hi]`` along ``axis`` in reverse order, i.e. ``jnp.flip`` of
    that static slice, built from single-index slices: Mosaic has no
    lowering for ``rev``, the primitive behind ``jnp.flip``."""
    parts = [jax.lax.slice_in_dim(x, i, i + 1, axis=axis)
             for i in range(hi - 1, lo - 1, -1)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=axis)


def _reflect_block(idx, total: int):
    """Reflect an out-of-range block index into [0, total): -1 -> 1,
    total -> total-2 (identity in range).  The non-periodic analogue of
    the ``% total`` wrap in the ring index maps -- chosen over clamping
    because it never fetches the same block on consecutive ring steps,
    so Pallas's consecutive-revisit dedup (and the audit's exact
    grid-bytes model) sees a fetch sequence identical to periodic.  The
    fetched edge content is then overwritten by the in-kernel boundary
    fills.  Works on plain ints (the auditor enumerates index maps) and
    traced ints (the launched kernel) alike; ring walks stay within one
    block of the domain, so a single reflection suffices.
    """
    if total == 1:
        return idx * 0
    last = total - 1
    return last - abs(last - abs(idx))


def _axis_block_index(idx, total: int, mode: str):
    """One ring-axis block index under its boundary mode: periodic wraps
    (the historical map, bit for bit), every other mode reflects."""
    return idx % total if mode == "periodic" else _reflect_block(idx, total)


def apply_boundary_fills(cur, modes, edges, halo: int, x_pad: int = 0,
                         x_tiled: bool = False):
    """Re-impose non-periodic boundary values on the halo of one region.

    ``cur`` is a halo-extended compute region whose axis ``ax`` carries
    ``halo`` out-of-domain cells per side on domain-edge cells (garbage
    as far as the mode is concerned: reflected-block fetches, stale
    carry, or host padding).  For every non-periodic axis this rebuilds
    those cells from the in-domain part -- zeros, the broadcast edge
    cell, or the mirrored rows -- gated per side by ``edges[ax]``
    (is_lo, is_hi) so interior cells keep their true fetched halo.
    Axes fill in ascending order, so later axes mirror already-filled
    earlier-axis halo cells: exactly ``np.pad``'s sequential corner
    semantics, which the oracle's ``pad_boundary`` shares.

    The last axis fills only when ``x_tiled`` (column-tiled kernels;
    full-width kernels re-extend via :func:`extend_columns` instead).
    ``x_pad`` is the remainder path's right-padding column count: the
    last tile's domain edge sits ``x_pad`` columns INSIDE the block, so
    its fill region shifts left by ``x_pad`` (the pad tail itself is
    left untouched -- it only feeds output columns that are sliced off).
    Free ops only (slice / flip / broadcast / select / concat): the
    traced-FLOP audit must count the same FLOPs as the periodic kernel.
    """
    if edges is None:
        return cur
    ndim = cur.ndim

    def sl(ax, a, b):
        s = [slice(None)] * ndim
        s[ax] = slice(a, b)
        return tuple(s)

    o = halo
    for ax in range(ndim):
        mode = modes[ax]
        last_axis = ax == ndim - 1
        if mode == "periodic" or o == 0 or (last_axis and not x_tiled):
            continue
        pad = x_pad if last_axis else 0
        valid = cur.shape[ax] - 2 * o - pad
        lo_flag, hi_flag = edges[ax]
        if mode == "zero":
            lo_fill = jnp.zeros_like(cur[sl(ax, 0, o)])
            hi_fill = jnp.zeros_like(cur[sl(ax, valid + o, valid + 2 * o)])
        elif mode == "replicate":
            reps = [1] * ndim
            reps[ax] = o
            lo_fill = jnp.tile(cur[sl(ax, o, o + 1)], reps)
            hi_fill = jnp.tile(cur[sl(ax, valid + o - 1, valid + o)], reps)
        elif mode == "reflect":
            lo_fill = _mirror(cur, o + 1, 2 * o + 1, ax)
            hi_fill = _mirror(cur, valid - 1, valid + o - 1, ax)
        else:
            raise ValueError(f"unknown boundary mode {mode!r}")
        lo = lo_fill if lo_flag is True \
            else jnp.where(lo_flag, lo_fill, cur[sl(ax, 0, o)])
        hi = hi_fill if hi_flag is True \
            else jnp.where(hi_flag, hi_fill,
                           cur[sl(ax, valid + o, valid + 2 * o)])
        parts = [lo, cur[sl(ax, o, valid + o)], hi]
        if pad:
            parts.append(cur[sl(ax, valid + 2 * o, None)])
        cur = jnp.concatenate(parts, axis=ax)
    return cur


def choose_tile(n: int, preferred: int = 128) -> int:
    """Column-tile width for the banded MXU contraction: min(n, preferred).

    Cap policy: the tile is NEVER degenerate -- widths that are not
    multiples of ``preferred`` get a full-size tile plus one narrower
    edge tile (both kernels handle the remainder by slicing the banded
    operand, which contains every narrower band as a leading submatrix).
    The historical rule searched for the largest divisor of ``n``, which
    collapsed to 1-wide tiles on prime widths (choose_tile(257) == 1)
    and to awkward off-lane tiles on near-misses (choose_tile(130) ==
    65), silently destroying MXU utilization.
    """
    if n <= 0:
        raise ValueError(f"width must be positive, got {n}")
    return min(n, preferred)


def choose_hblock(strip_m: int, halo: int, align: int = SUBLANE) -> int:
    """Halo-block height: smallest multiple of ``align`` dividing strip_m
    and >= max(halo, strip/16); strip_m itself when there is none.

    ``h_block`` must cover the halo in one neighbor block (>= halo) and
    divide the strip.  Smaller blocks cut traffic (amplification is
    1 + 2h/strip_m) but multiply grid cells, so we floor at
    ceil(strip_m/16) -- amplification lands at ~1.125 whenever the halo
    allows, and degrades gracefully toward 3x (h_block = strip_m) as the
    halo forces h_block up.  ``align`` is the tiling rule of the axis the
    block sits on: the sublane tile for the row axis (8 for 32-bit,
    ``sublane_tile``), ``LANE`` for the lane axis, 1 for a leading axis
    (z), which Mosaic does not tile.
    """
    if strip_m <= 0:
        raise ValueError(f"strip height must be positive, got {strip_m}")
    floor = max(halo, -(-strip_m // 16))      # integer ceil division
    cands = [d for d in range(align, strip_m + 1, align)
             if strip_m % d == 0 and d >= floor]
    return min(cands) if cands else strip_m


def _strip_working_set(d: int, hb: int, n: int, halo: int,
                       dtype_bytes: int) -> int:
    """Full-width 2D VMEM working set: the ring's scratch + in-flight
    h-block (floored at three full strips, below), plus the
    horizontally-extended f32 compute tile and the output strip."""
    # 3*d*n: the deleted whole-strip foil's pricing (ROADMAP Speed 2).
    inputs = max(3 * d * n, (d + 2 * hb) * n + hb * n)
    return (inputs + (d + 2 * halo) * (n + 2 * halo) + d * n) * dtype_bytes


def _col_working_set_2d(sm: int, hb: int, wt: int, wb: int, halo: int,
                        x_halo: int, dtype_bytes: int) -> int:
    """Column-tiled 2D VMEM working set: scratch + in-flight block +
    halo-extended compute tile + output tile."""
    scratch = (sm + 2 * hb) * (wt + 2 * wb) + hb * wb
    compute = (sm + 2 * halo) * (wt + 2 * x_halo)
    return (scratch + compute + sm * wt) * dtype_bytes


def _wtile_candidates(w: int, x_halo: int) -> list:
    """Column-tile widths worth considering for a width-``w`` grid: the
    multiples of ``LANE`` below ``w`` (a lane-axis block must be one)
    that divide ``w`` (the aligned path: pure modulo-wrap column walk,
    zero host traffic) and hold the x-halo, plus ``k*LANE`` for k in
    (1, 2, 4) -- non-divisor tiles run the edge-tile remainder path, so
    prime and awkward widths still get a full-size tile.  ``w`` itself
    is excluded: that is the full-width fast path, not a column tiling.
    Empty when ``w <= LANE``: such a grid cannot be column-tiled.
    """
    cands = {d for d in range(LANE, w, LANE) if w % d == 0 and d >= x_halo}
    cands.update(k * LANE for k in (1, 2, 4) if k * LANE < w)
    return sorted(cands)


def choose_strip_blocks(
    h: int,
    n: int,
    halo: int,
    dtype_bytes: int = 4,
    vmem_budget: int = None,
    preferred: int = 128,
) -> tuple:
    """Jointly size the full-width (strip_m, h_block) under the VMEM budget.

    ``strip_m``: a divisor of ``h``, >= halo, fitting VMEM, and a multiple
    of the dtype's sublane tile unless it is ``h`` itself (the tiling
    rule); among fitting divisors prefer the largest <= ``preferred``
    (taller strips both amortize per-cell cost and shrink the halo read
    factor 1 + 2h/strip_m).
    ``h_block``: ``choose_hblock`` of the chosen strip; the working set
    is ``_strip_working_set``'s.  When NO full-width strip fits, the
    smallest viable one is returned anyway -- ``resolve_strip_blocks``
    detects that case and escalates to the column-tiled sizing
    (``choose_col_blocks``) instead.
    """
    if vmem_budget is None:
        vmem_budget = vmem_budget_bytes()
    sub = sublane_tile(dtype_bytes)

    def working_set(d: int) -> int:
        return _strip_working_set(d, choose_hblock(d, halo, sub), n, halo,
                                  dtype_bytes)

    viable = _axis_candidates(h, halo, None, h, sub)
    fitting = [d for d in viable if working_set(d) <= vmem_budget]
    pool = fitting or [min(viable)]
    under = [d for d in pool if d <= preferred]
    strip_m = max(under) if under else min(pool)
    return strip_m, choose_hblock(strip_m, halo, sub)


def choose_strip(
    h: int,
    n: int,
    halo: int,
    dtype_bytes: int = 4,
    vmem_budget: int = None,
    preferred: int = 128,
) -> int:
    """Strip height only (see ``choose_strip_blocks`` for the joint choice)."""
    return choose_strip_blocks(h, n, halo, dtype_bytes, vmem_budget,
                               preferred)[0]


def _axis_candidates(extent: int, halo: int, pin: int,
                     preferred: int = 128, align: int = 1) -> list:
    """Tile candidates along one axis: divisors >= halo that are
    multiples of ``align`` (or the whole extent), capped at
    ``preferred`` (pins pass through verbatim)."""
    if pin is not None:
        return [pin]
    cands = [d for d in range(1, extent + 1)
             if extent % d == 0 and d >= halo
             and (d % align == 0 or d == extent)] or [extent]
    capped = [d for d in cands if d <= preferred]
    return capped or [min(cands)]


def choose_col_blocks(
    h: int,
    w: int,
    halo: int,
    x_halo: int = None,
    dtype_bytes: int = 4,
    vmem_budget: int = None,
    preferred: int = 128,
    m_pin: int = None,
    w_pin: int = None,
) -> tuple:
    """Jointly size the column-tiled 2D geometry
    (strip_m, h_block, w_tile, w_block) under the VMEM budget.

    Entered when the full-width working set cannot fit (or the caller
    pinned ``w_tile``): the search spans strip candidates (divisors of
    ``h`` >= halo, capped at ``preferred``) x column-tile candidates
    (``_wtile_candidates``); blocks are ``choose_hblock`` of each tile,
    with the w-block floored at the CARRIED x-halo ``x_halo`` (= t*r --
    column-tiled kernels cannot re-wrap, DESIGN.md §10).  Among fitting
    combinations the rule minimizes the read-amplification product
    (1 + 2*h_block/strip_m)(1 + 2*w_block/w_tile), tie-breaking toward
    fewer grid cells (larger tiles); when nothing fits, the smallest
    working set wins.
    """
    if vmem_budget is None:
        vmem_budget = vmem_budget_bytes()
    xh = halo if x_halo is None else x_halo
    sub = sublane_tile(dtype_bytes)

    def hb_of(sm: int) -> int:
        return choose_hblock(sm, halo, sub)

    def wb_of(wt: int) -> int:
        return choose_hblock(wt, max(xh, 1), LANE)

    def ws(sm: int, wt: int) -> int:
        return _col_working_set_2d(sm, hb_of(sm), wt, wb_of(wt), halo, xh,
                                   dtype_bytes)

    def amp(sm: int, wt: int) -> float:
        return (substrate_read_amp(sm, hb_of(sm))
                * substrate_read_amp(wt, wb_of(wt)))

    strips = _axis_candidates(h, halo, m_pin, preferred, sub)
    w_cands = [w_pin] if w_pin else _wtile_candidates(w, xh)
    if not w_cands:
        # Too narrow to column-tile (w <= LANE): the thinnest full-width
        # strip is the only legal geometry left.
        return min(strips), hb_of(min(strips)), 0, 0
    pairs = [(sm, wt) for sm in strips for wt in w_cands]
    fitting = [p for p in pairs if ws(*p) <= vmem_budget]
    pool = fitting or [min(pairs, key=lambda p: ws(*p))]
    sm, wt = min(pool, key=lambda p: (amp(*p), -p[0] * p[1]))
    return sm, hb_of(sm), wt, wb_of(wt)


def _block_candidates(tile: int, halo: int, align: int) -> list:
    """Ring-block heights for one axis of a ``tile``-wide cell: every
    divisor of ``tile`` that covers the halo and is a multiple of the
    axis alignment (1 for z, the sublane tile for rows, ``LANE`` for
    columns); the whole tile when none is."""
    return [d for d in range(align, tile + 1, align)
            if tile % d == 0 and d >= halo] or [tile]


def _slab_working_set(geom: "SubstrateGeom", n: int, halo: int,
                      x_halo: int, dtype_bytes: int) -> int:
    """VMEM bytes one 3D launch holds: the ring's scratch, the in-flight
    block and the output slab in the grid's dtype, plus the f32
    halo-extended compute slab (full width re-wraps x in VMEM;
    column-tiled cells carry the ``x_halo``)."""
    zs, zb, sm, hb = geom.z_slab, geom.z_block, geom.strip_m, geom.h_block
    if geom.w_tile:
        wt, wb = geom.w_tile, geom.w_block
        ring_w, block_w, comp_w, out_w = wt + 2 * wb, wb, wt + 2 * x_halo, wt
    else:
        ring_w, block_w, comp_w, out_w = n, n, n + 2 * halo, n
    held = ((zs + 2 * zb) * (sm + 2 * hb) * ring_w + zb * hb * block_w
            + zs * sm * out_w)
    return held * dtype_bytes + (zs + 2 * halo) * (sm + 2 * halo) * comp_w * 4


def slab_walk_cost(grid_shape, geom: "SubstrateGeom",
                   dtype_bytes: int) -> int:
    """What the 3D sizing minimizes, in HBM bytes: one call's ring reads
    (``hbm_read_bytes_per_step_3d``) plus its grid steps
    (``substrate_grid_steps``) at ``STEP_BYTES`` each."""
    return (hbm_read_bytes_per_step_3d(grid_shape, geom, dtype_bytes)
            + substrate_grid_steps(grid_shape, geom) * STEP_BYTES)


def choose_slab_blocks(
    z: int,
    h: int,
    n: int,
    halo: int,
    dtype_bytes: int = 4,
    vmem_budget: int = None,
    preferred: int = 128,
    z_pin: int = None,
    m_pin: int = None,
    w_pin: int = None,
    x_halo: int = None,
    zb_pin: int = None,
    hb_pin: int = None,
    wb_pin: int = None,
) -> tuple:
    """Jointly size the 3D geometry
    (z_slab, z_block, strip_m, h_block, w_tile, w_block) by what one
    call's ring walk costs (``slab_walk_cost``):

        cost = ring read bytes + grid steps * STEP_BYTES

    ``z_slab`` divides Z and ``strip_m`` divides H, both >= halo (free
    axes capped at ``preferred``); each block height is any divisor of
    its tile that covers the halo and meets the axis alignment
    (``_block_candidates``).  Ties go to the smaller halo-extended
    compute slab per output point, its rows padded to whole sublane tiles
    as the vector unit computes them (less fused-step recompute), then to
    larger cells, then to taller strips.  Only geometries whose
    ``_slab_working_set`` fits the VMEM budget compete.  Two phases:

      * FULL WIDTH (w_tile = w_block = 0, the fast path): blocks span W.
        Taken whenever any full-width geometry fits (or ``w_pin=0``
        forces it).
      * COLUMN-TILED (DESIGN.md §10): when none fits (or ``w_pin`` > 0),
        the search adds the (w_tile, w_block) axis --
        ``_wtile_candidates`` of W, w_block covering the carried x-halo
        ``x_halo`` (= t*r) -- and the column ring multiplies the steps.

    When nothing fits, the smallest working set wins.  The ``*_pin``
    arguments fix axes to explicit user pins: the search sizes only the
    FREE axes conditioned on the pins.  Pins are exempt from the
    divisor/halo/``preferred`` filters (explicit values are validated
    strictly by the caller).
    """
    if vmem_budget is None:
        vmem_budget = vmem_budget_bytes()
    xh = halo if x_halo is None else x_halo
    sub = sublane_tile(dtype_bytes)

    def blocks(tile: int, pin: int, blk_halo: int, align: int) -> list:
        return [pin] if pin else _block_candidates(tile, blk_halo, align)

    def ws(g: SubstrateGeom) -> int:
        return _slab_working_set(g, n, halo, xh, dtype_bytes)

    def rank(g: SubstrateGeom) -> tuple:
        rows = -(-(g.strip_m + 2 * halo) // sub) * sub
        extended = (g.z_slab + 2 * halo) * rows / (g.z_slab * g.strip_m)
        if g.w_tile:
            extended *= (g.w_tile + 2 * xh) / g.w_tile
        return (slab_walk_cost((z, h, n), g, dtype_bytes), extended,
                -g.z_slab * g.strip_m * (g.w_tile or n), -g.strip_m)

    def search(w_cands: list) -> tuple:
        cands = [SubstrateGeom(dim=3, strip_m=sm, h_block=hb, z_slab=zs,
                               z_block=zb, w_tile=wt, w_block=wb)
                 for zs in _axis_candidates(z, halo, z_pin, preferred)
                 for zb in blocks(zs, zb_pin, halo, 1)
                 for sm in _axis_candidates(h, halo, m_pin, preferred, sub)
                 for hb in blocks(sm, hb_pin, halo, sub)
                 for wt in w_cands
                 for wb in (blocks(wt, wb_pin, max(xh, 1), LANE) if wt
                            else (0,))]
        fitting = [g for g in cands if ws(g) <= vmem_budget]
        return min(fitting, key=rank) if fitting else None, cands

    def fields(g: SubstrateGeom) -> tuple:
        return g.z_slab, g.z_block, g.strip_m, g.h_block, g.w_tile, g.w_block

    w_cands = [w_pin] if w_pin else _wtile_candidates(n, xh)
    if not w_pin:
        best, cands = search([0])
        if best is not None:
            return fields(best)
        if w_pin == 0 or not w_cands:
            return fields(min(cands, key=ws))
    best, cands = search(w_cands)
    return fields(best if best is not None else min(cands, key=ws))


@dataclasses.dataclass(frozen=True)
class SubstrateGeom:
    """Resolved halo-plane substrate geometry for one kernel launch.

    ``dim`` is the grid rank (1D executes lifted through the 2D substrate
    with strip_m=1 and zero vertical halo).  Both block heights are >=
    the halo and divide their tile.  ``w_tile=0`` is the full-width fast
    path; ``w_tile > 0`` selects the column-tiled substrate (DESIGN.md
    §10, with ``w_block`` >= the carried x-halo t*r and dividing
    ``w_tile``).  ``grid_steps`` counts the launch's grid steps on the
    grid the geometry was resolved for (``substrate_grid_steps``); it is
    0 where pricing had no grid.
    """

    dim: int
    strip_m: int
    h_block: int
    z_slab: int = 1              # 3D only; 1 otherwise
    z_block: int = 0             # 3D only; 0 otherwise
    w_tile: int = 0              # 0 = full width (fast path)
    w_block: int = 0             # column halo block; 0 iff w_tile == 0
    grid_steps: int = 0          # launch grid steps per call; 0 = no grid

    @property
    def read_amp(self) -> float:
        """Analytic grid-read amplification of this geometry (DESIGN.md
        §9/§10): 1 (lifted 1D), 1 + 2h/strip_m (2D), times
        (1 + 2z_block/z_slab) (3D), times (1 + 2w_block/w_tile) when
        column-tiled."""
        if self.dim == 1:
            return 1.0
        amp = substrate_read_amp(self.strip_m, self.h_block)
        if self.dim == 3:
            amp *= substrate_read_amp(self.z_slab, self.z_block)
        if self.w_tile:
            amp *= substrate_read_amp(self.w_tile, self.w_block)
        return amp

    def describe(self) -> str:
        """The substrate clause of decision reason strings -- formatted
        from resolved numbers only, so ``ops.explain`` and plan decisions
        agree verbatim whenever they resolve the same geometry.  A 3D
        clause resolved on a grid also counts its ``grid_steps``, which
        the 3D sizing prices (the 2D sizing does not)."""
        if self.dim == 3:
            geo = (f"z_slab={self.z_slab}, z_block={self.z_block}, "
                   f"strip_m={self.strip_m}, h_block={self.h_block}")
        elif self.dim == 1:
            geo = f"1D lifted, strip_m={self.strip_m}"
        else:
            geo = f"strip_m={self.strip_m}, h_block={self.h_block}"
        if self.dim >= 2:
            if self.w_tile:
                geo += f", w_tile={self.w_tile}, w_block={self.w_block}"
            else:
                geo += ", w_tile=full"
        steps = (f", grid_steps={self.grid_steps}"
                 if self.dim == 3 and self.grid_steps else "")
        return f"substrate read_amp={self.read_amp:.3f}x{steps} ({geo})"


def substrate_grid_steps(grid_shape, geom: SubstrateGeom) -> int:
    """Grid steps of one launch of ``geom`` over ``grid_shape``: each
    output cell walks its own blocks plus one neighbor block per side on
    every ringed axis; the lifted 1D launch is one step."""
    if geom.dim == 1 or len(grid_shape) == 1:
        return 1
    cells = grid_shape[-2] // geom.strip_m
    rings = [(geom.strip_m, geom.h_block)]
    if geom.dim == 3:
        cells *= grid_shape[0] // geom.z_slab
        rings.append((geom.z_slab, geom.z_block))
    if geom.w_tile:
        cells *= -(-grid_shape[-1] // geom.w_tile)
        rings.append((geom.w_tile, geom.w_block))
    return cells * math.prod(tile // block + 2 for tile, block in rings)


def _check_block_pins(h_block: int, z_block: int) -> None:
    """Refuse zero block pins: every launch walks a block ring, and a
    ring block holds at least one row (plane).  The coarsest ring is
    ``h_block = strip_m`` (three full strips per output strip, read amp
    3) and, in 3D, ``z_block = z_slab`` beside it (nine full slabs, read
    amp 9).  Both ``resolve_substrate_geom`` and ``pricing_geom`` check
    here first."""
    if h_block == 0:
        raise ValueError(
            "h_block=0 names no block ring; pin h_block=strip_m for the "
            "whole-strip read pattern")
    if z_block == 0:
        raise ValueError(
            "z_block=0 names no block ring; pin z_block=z_slab (with "
            "h_block=strip_m) for the whole-slab read pattern")


def _resolve_w_block(w_tile: int, w_block: int, x_halo: int) -> tuple:
    """(w_tile, w_block) under the shared pin rules: ``w_tile`` in
    (None, 0) is the full-width fast path (w_block forced 0; a lone
    w_block pin is rejected); a positive ``w_tile`` gets
    ``choose_hblock`` of the tile floored at the carried x-halo unless
    ``w_block`` is pinned too.  Both ``resolve_substrate_geom`` and
    ``pricing_geom`` route through here, so plan building and grid-free
    pricing can never disagree.
    """
    if not w_tile:
        if w_block:
            raise ValueError(
                f"w_block={w_block} without a w_tile names no substrate; "
                "pin w_tile too (or drop both for full width)")
        return 0, 0
    if w_block is None or w_block == 0:
        return w_tile, choose_hblock(w_tile, max(x_halo, 1), LANE)
    return w_tile, w_block


def pricing_geom(dim: int, halo: int, strip_m: int = 128,
                 h_block: int = None, z_slab: int = None,
                 z_block: int = None, w_tile: int = None,
                 w_block: int = None, grid_shape=None) -> SubstrateGeom:
    """Geometry resolution for pricing paths, which are handed the
    resolved numbers and need no grid to size against: dim 1 is always
    the lifted substrate; dim 2 takes ``strip_m`` as given with
    ``choose_hblock`` filling ``h_block``; dim 3 defaults ``z_slab`` to
    ``strip_m`` and ``z_block`` to ``choose_hblock`` of it.  ``w_tile``
    in (None, 0) prices the full-width fast path; a positive ``w_tile``
    prices the column-tiled substrate (w_block auto-resolved at the
    fused x-halo ``halo`` unless pinned).  With ``grid_shape`` the
    geometry also counts its launch's ``grid_steps`` on that grid."""
    _check_block_pins(h_block, z_block)
    if dim not in (1, 2, 3):
        raise ValueError(f"substrate supports 1D/2D/3D grids, got dim {dim}")
    if dim == 1:
        geom = SubstrateGeom(dim=1, strip_m=1, h_block=1)
    else:
        hb = choose_hblock(strip_m, halo) if h_block is None else h_block
        wt, wb = _resolve_w_block(w_tile, w_block, halo)
        geom = SubstrateGeom(dim=2, strip_m=strip_m, h_block=hb,
                             w_tile=wt, w_block=wb)
    if dim == 3:
        zs = strip_m if z_slab is None else z_slab
        zb = z_block if z_block is not None else choose_hblock(zs, halo, 1)
        geom = dataclasses.replace(geom, dim=3, z_slab=zs, z_block=zb)
    return _with_grid_steps(geom, grid_shape)


def _with_grid_steps(geom: SubstrateGeom, grid_shape) -> SubstrateGeom:
    """``geom`` with its ``grid_steps`` on ``grid_shape`` (None: as is)."""
    if grid_shape is None:
        return geom
    return dataclasses.replace(
        geom, grid_steps=substrate_grid_steps(grid_shape, geom))


def _normalize_w_pin(w_tile, w_block, wid: int):
    """Clamp explicit width pins to the grid: ``w_tile >= W`` IS the
    full-width fast path (existing geometry bit-for-bit unchanged)."""
    if w_tile is not None and w_tile >= wid:
        return 0, 0
    return w_tile, w_block


def resolve_substrate_geom(grid_shape, halo: int, dtype_bytes: int,
                           tile_m: int = None, h_block: int = None,
                           z_slab: int = None, z_block: int = None,
                           w_tile: int = None, w_block: int = None,
                           x_halo: int = None) -> SubstrateGeom:
    """Resolve the full substrate geometry from possibly-``None`` requests.

    THE shared N-D auto-sizing rule: the kernels, ``stencil_plan`` pricing
    and ``registry.PlanContext.resolve_geom`` all call this, so plan-level
    and kernel-level sizing can never drift apart.  Rank comes from
    ``len(grid_shape)``:

      * 1D: lifted 2D geometry (strip_m=1, zero vertical halo, read amp 1;
        never column-tiled);
      * 2D: exactly ``resolve_strip_blocks`` (z fields stay inert);
      * 3D: ``choose_slab_blocks``, which prices each call's ring walk;
        explicit ``tile_m`` / ``z_slab`` are clamped to the grid, and every
        pin conditions the search of the free axes.

    ``h_block=0`` and ``z_block=0`` are refused (``_check_block_pins``).
    The result carries its ``grid_steps`` on ``grid_shape``.

    Width (DESIGN.md §10): ``w_tile=None`` auto-resolves -- full width
    whenever the full-width working set fits the VMEM budget, the
    column-tiled substrate otherwise; ``w_tile=0`` (or >= W) pins full
    width; a positive ``w_tile`` pins the column tile.  ``x_halo`` is the
    CARRIED per-side x-halo of column-tiled fused execution (t*r; the
    column-tiled kernels cannot re-wrap partial rows) and defaults to
    ``halo`` -- exact for the square kernels this repo builds.
    """
    _check_block_pins(h_block, z_block)
    grid_shape = tuple(grid_shape)
    dim = len(grid_shape)
    if dim == 1:
        return _with_grid_steps(SubstrateGeom(dim=1, strip_m=1, h_block=1),
                                grid_shape)
    xh = halo if x_halo is None else x_halo
    if dim == 2:
        strip_m, hb, wt, wb = resolve_strip_blocks(
            grid_shape, halo, dtype_bytes, tile_m, h_block,
            w_tile, w_block, xh)
        return _with_grid_steps(
            SubstrateGeom(dim=2, strip_m=strip_m, h_block=hb, w_tile=wt,
                          w_block=wb), grid_shape)
    if dim != 3:
        raise ValueError(f"substrate supports 1D/2D/3D grids, got rank {dim}")
    z, h, wid = grid_shape
    w_tile, w_block = _normalize_w_pin(w_tile, w_block, wid)
    if w_block and w_tile is None:
        _resolve_w_block(0, w_block, xh)    # raises: lone w_block pins are
        # rejected on every path (see resolve_strip_blocks)
    # One pin-aware joint search: a pinned axis is fixed (clamped to the
    # grid) and only the free axes are sized -- conditioned on the pins, so
    # the VMEM fit and the priced walk always describe the geometry that
    # actually runs.
    zs, zb, sm, hb, wt, auto_wb = choose_slab_blocks(
        z, h, wid, halo, dtype_bytes,
        z_pin=min(z_slab, z) if z_slab is not None else None,
        m_pin=min(tile_m, h) if tile_m is not None else None,
        w_pin=w_tile, x_halo=xh, zb_pin=z_block, hb_pin=h_block,
        wb_pin=w_block or None)
    wt, wb = _resolve_w_block(wt, w_block if w_block else auto_wb, xh)
    return _with_grid_steps(
        SubstrateGeom(dim=3, strip_m=sm, h_block=hb, z_slab=zs, z_block=zb,
                      w_tile=wt, w_block=wb), grid_shape)


def _check_wrap_radius(w: int, r: int, mode: str = "periodic") -> None:
    """THE per-axis radius guard, shared by every rank's validation branch
    (historically copy-pasted across the 1D/2D/3D paths).

    Periodic axes wrap, so only ``w < r`` is impossible (the historical
    check, message unchanged).  Non-periodic axes have no wrap at all:
    a stencil whose support reaches across the whole axis (``r >= w``)
    would read nothing but synthesized boundary cells, so it raises with
    a mode-specific message instead of the misleading "lower the
    radius" wrap phrasing.
    """
    if mode == "periodic":
        if w < r:
            raise ValueError(
                f"wrap radius {r} exceeds grid width {w}; lower the radius")
        return
    if r >= w:
        raise ValueError(
            f"stencil radius {r} spans the whole {mode!r} axis "
            f"(extent {w}); a non-periodic axis needs extent > radius "
            "-- enlarge the grid or use a narrower stencil")


def _check_reflect_extent(extent: int, halo: int, axis: str,
                          mode: str) -> None:
    """Reflect needs ``halo`` in-domain mirror cells beyond the edge cell:
    cell ``-k`` reads cell ``+k``, so the axis extent must exceed the
    total (fused) halo depth."""
    if mode == "reflect" and extent < halo + 1:
        raise ValueError(
            f"reflect boundary on the {axis} axis needs extent >= "
            f"halo+1 = {halo + 1}, got {extent}; mirror cells would "
            "fall outside the domain")


def validate_tiling(shape, strip_m: int, tile_n: int, halo: int,
                    radius: int = None, h_block: int = None,
                    z_slab: int = None, z_block: int = None,
                    w_tile: int = None, w_block: int = None,
                    x_halo: int = None, boundary=None) -> None:
    """Halo-plane substrate tiling constraints (1D, 2D and 3D grids).

    ``strip_m`` is the strip height (rows per output block); ``tile_n`` is
    the column-chunk width of the banded MXU contraction (pass the full
    width for the VPU path) -- any width in [1, W] is legal, the kernels
    handle a narrower final chunk by slicing the banded operand.
    ``radius`` is the per-step wrap radius (defaults to ``halo`` for
    callers that run a single step at the full radius).  ``h_block`` must
    divide ``strip_m`` and cover the vertical halo.  3D grids
    additionally constrain ``z_slab`` (divides Z, >= halo) and
    ``z_block`` (divides ``z_slab``, >= halo); ``None`` blocks are not
    checked.  Column-tiled launches (``w_tile`` > 0, DESIGN.md §10)
    require a ``w_block`` that divides ``w_tile`` and covers the CARRIED
    x-halo ``x_halo`` (= t*r; defaults to ``halo``) --
    ``w_tile`` need NOT divide W (edge tiles run the remainder path).
    ``boundary`` is the per-axis mode spec (DESIGN.md §15): non-periodic
    axes swap the wrap-radius guard for the mode-specific one, and
    reflect axes additionally need extent >= halo+1 (the mirror depth).
    """
    r = halo if radius is None else radius
    w = shape[-1]
    modes = resolve_boundary(boundary, len(shape))
    if len(shape) == 1:
        # Lifted-1D: no vertical support, so only the x-axis guard binds.
        _check_wrap_radius(w, r, modes[-1])
        _check_reflect_extent(w, halo, "x", modes[-1])
        return
    if len(shape) == 3:
        z, h, w = shape
        zs = z if z_slab is None else z_slab
        if z % zs:
            raise ValueError(
                f"grid depth {z} not divisible by z_slab {zs}")
        if zs < halo:
            raise ValueError(
                f"halo {halo} exceeds z_slab {zs}; "
                "lower fusion depth or enlarge slabs")
        if z_block:
            if zs % z_block:
                raise ValueError(
                    f"z_block {z_block} does not divide z_slab {zs}")
            if z_block < halo:
                raise ValueError(
                    f"halo {halo} exceeds z_block {z_block}; "
                    "enlarge z_block or lower fusion depth")
    else:
        h, w = shape
    if h % strip_m:
        raise ValueError(
            f"grid {shape} rows not divisible by strip height {strip_m}")
    if not 1 <= tile_n <= w:
        raise ValueError(
            f"column tile {tile_n} outside [1, {w}] for grid {shape}")
    if strip_m < halo:
        raise ValueError(
            f"halo {halo} exceeds strip height {strip_m}; "
            "lower fusion depth or enlarge strips"
        )
    if h_block:
        if strip_m % h_block:
            raise ValueError(
                f"h_block {h_block} does not divide strip height {strip_m}"
            )
        if h_block < halo:
            raise ValueError(
                f"halo {halo} exceeds h_block {h_block}; "
                "enlarge h_block or lower fusion depth"
            )
    if w_tile:
        if w_tile > w:
            raise ValueError(
                f"w_tile {w_tile} exceeds grid width {w}")
        xh = halo if x_halo is None else x_halo
        if not w_block:
            raise ValueError(
                f"column tiling needs w_block >= the carried x-halo {xh}")
        if w_tile % w_block:
            raise ValueError(
                f"w_block {w_block} does not divide w_tile {w_tile}")
        if w_block < xh:
            raise ValueError(
                f"carried x-halo {xh} exceeds w_block {w_block}; "
                "enlarge w_block or lower fusion depth")
    _check_wrap_radius(w, r, modes[-1])
    _check_reflect_extent(w, halo, "x", modes[-1])
    lead = shape[:-1]
    for extent, mode, name in zip(lead, modes[:-1],
                                  ("z", "y")[-len(lead):]):
        # Periodic leading axes never had a radius guard (any extent
        # wraps -- the 1D lift runs extent 1) -- keep that bit of
        # history; non-periodic axes get the mode-specific guards.
        if mode == "periodic":
            continue
        _check_wrap_radius(extent, r, mode)
        _check_reflect_extent(extent, halo, name, mode)


#: Exact-arity all-zero index-map factories for grid-constant operands
#: (banded weights): Pallas wants the index map's arity to match the grid
#: rank, and the operand's block index never moves.
_ZERO_INDEX_MAPS = {
    1: lambda z: (lambda i: z),
    2: lambda z: (lambda i, j: z),
    3: lambda z: (lambda i, j, k: z),
    4: lambda z: (lambda i, j, k, l: z),
}


@dataclasses.dataclass(frozen=True)
class LaunchGeometry:
    """Complete, introspectable description of ONE Pallas substrate launch.

    Everything the launchers hand to ``pl.pallas_call`` -- the grid, the
    input/output block shapes and their index maps, the VMEM scratch
    shape, the mixed-radix ring decomposition that turns the last grid
    axis into scratch write slots, the compute fire step and the
    halo-extended read window -- lives here as data, built by
    ``strip_launch_geometry`` / ``slab_launch_geometry`` and consumed by
    ``_launch``.  ``repro.audit`` enumerates the SAME object statically
    (the index maps are pure-Python closures over ints, so calling them
    with concrete grid indices is exact enumeration, no tracing): the
    audited geometry IS the launched geometry, never a re-derivation.

    ``kind`` is one of "flat", "subblocked", "coltiled",
    "slab_subblocked", "slab_coltiled".  Every launch reads its input
    through ONE BlockSpec (``in_block``, ``in_index_map``).  Ring kinds
    (all but "flat", the lifted 1D launch) have a scratch and put the
    ring on the LAST grid axis; ``ring_dims`` is its row-major
    mixed-radix shape (e.g. (nb+2, ring_w)) and ``block_dims`` the
    matching per-ringed-axis scratch block sizes.
    ``out_shape`` is the launch output BEFORE the remainder-path column
    slice; ``src_shape`` the input AFTER any host-side column extension
    (``_extend_columns_for_tiling``) -- both equal the grid shape on
    aligned launches.
    """

    kind: str
    grid: tuple
    in_block: tuple
    in_index_map: object
    out_block: tuple
    out_index_map: object
    out_shape: tuple
    src_shape: tuple
    halo: int
    x_halo: int
    scratch_shape: tuple = None
    ring_dims: tuple = ()
    block_dims: tuple = ()
    read_bounds: tuple = ()      # per-scratch-axis (lo, hi) compute window
    aligned: bool = True
    boundary: tuple = ()         # per-grid-axis modes; () = all periodic

    @property
    def periodic(self) -> bool:
        """True iff every axis wraps (the historical substrate)."""
        return all(m == "periodic" for m in self.boundary)

    @property
    def ring(self) -> int:
        """Grid steps per output cell (1 when there is no ring axis)."""
        return math.prod(self.ring_dims) if self.ring_dims else 1

    @property
    def fire_step(self) -> int:
        """Ring step on which compute fires: always the LAST ring step
        (every scratch slot must be written before the halo-extended
        read -- the invariant the scratch dependence audit proves)."""
        return self.ring - 1

    @property
    def cells(self) -> int:
        """Output cells in the launch (= grid size / ring length)."""
        return math.prod(self.grid) // self.ring

    def ring_indices(self, j):
        """Row-major mixed-radix decomposition of ring step ``j`` over
        ``ring_dims`` (last axis fastest).  Works on traced ints inside
        the kernel and on plain ints inside the auditor."""
        idxs = []
        for d in reversed(self.ring_dims):
            idxs.append(j % d)
            j = j // d
        return tuple(reversed(idxs))

    def scratch_slot(self, j):
        """Per-ringed-axis (start, size) scratch write slot of ring step
        ``j``; trailing (full-width) scratch axes are not listed."""
        return tuple((k * b, b)
                     for k, b in zip(self.ring_indices(j), self.block_dims))


def strip_launch_geometry(x_shape, strip_m: int, h_block: int, halo: int,
                          w_tile: int = 0, w_block: int = 0,
                          x_halo: int = 0,
                          boundary=None) -> LaunchGeometry:
    """Build the 2D (and lifted-1D) launch geometry: the single source of
    truth for what ``strip_substrate_call`` launches.

    ``halo=0`` -> "flat" (one load per strip, read amp exactly 1);
    otherwise "subblocked" ((strip, h-block) ring into VMEM scratch);
    ``w_tile>0`` -> "coltiled" (DESIGN.md §10, full 2-axis block ring,
    edge-tile remainder path on non-dividing widths).

    ``boundary`` is the per-axis (rows, cols) mode pair: non-periodic
    axes reflect out-of-range block indices at block granularity
    (``_reflect_block``) instead of wrapping -- same fetch count, all in
    bounds, content overwritten by the kernels' in-kernel fills.
    """
    h, n = x_shape
    by, bx = resolve_boundary(boundary, 2)
    gm = h // strip_m
    if w_tile:
        nb = strip_m // h_block
        nbw = w_tile // w_block
        ring_w = nbw + 2
        gw = -(-n // w_tile)
        aligned = n % w_tile == 0
        total_h = h // h_block
        if aligned:
            total_w = n // w_block
            src_shape, out_w = (h, n), n

            def col_index(iw, jw):
                return _axis_block_index(iw * nbw + jw - 1, total_w, bx)
        else:
            src_shape = (h, gw * w_tile + 2 * w_block)
            out_w = gw * w_tile

            def col_index(iw, jw):
                return iw * nbw + jw   # the extension carries the boundary

        lg = LaunchGeometry(
            kind="coltiled",
            grid=(gm, gw, (nb + 2) * ring_w),
            in_block=(h_block, w_block),
            in_index_map=lambda i, iw, j: (
                _axis_block_index(i * nb + j // ring_w - 1, total_h, by),
                col_index(iw, j % ring_w)),
            out_block=(strip_m, w_tile),
            out_index_map=lambda i, iw, j: (i, iw),
            out_shape=(h, out_w),
            src_shape=src_shape,
            halo=halo, x_halo=x_halo,
            scratch_shape=(strip_m + 2 * h_block, w_tile + 2 * w_block),
            ring_dims=(nb + 2, ring_w),
            block_dims=(h_block, w_block),
            read_bounds=((h_block - halo, h_block + strip_m + halo),
                         (w_block - x_halo, w_block + w_tile + x_halo)),
            aligned=aligned,
            boundary=(by, bx),
        )
    elif halo == 0:
        # No vertical halo => no neighbor loads: one load per strip, read
        # amp exactly 1.
        lg = LaunchGeometry(
            kind="flat", grid=(gm,),
            in_block=(strip_m, n),
            in_index_map=lambda i: (i, 0),
            out_block=(strip_m, n),
            out_index_map=lambda i: (i, 0),
            out_shape=(h, n), src_shape=(h, n), halo=0, x_halo=x_halo,
            boundary=(by, bx),
        )
    else:
        nb = strip_m // h_block
        total = h // h_block
        lg = LaunchGeometry(
            kind="subblocked", grid=(gm, nb + 2),
            in_block=(h_block, n),
            in_index_map=lambda i, j: (
                _axis_block_index(i * nb + j - 1, total, by), 0),
            out_block=(strip_m, n),
            out_index_map=lambda i, j: (i, 0),
            out_shape=(h, n), src_shape=(h, n), halo=halo, x_halo=x_halo,
            scratch_shape=(strip_m + 2 * h_block, n),
            ring_dims=(nb + 2,), block_dims=(h_block,),
            read_bounds=((h_block - halo, h_block + strip_m + halo),
                         (0, n)),
            boundary=(by, bx),
        )
    from repro.testing.faults import corrupt_geometry
    return corrupt_geometry(lg)


def slab_launch_geometry(x_shape, geom: SubstrateGeom, halo: int,
                         x_halo: int = 0, boundary=None) -> LaunchGeometry:
    """Build the 3D launch geometry: the single source of truth for what
    ``slab_substrate_call`` launches ("slab_subblocked" /
    "slab_coltiled", mirroring the 2D kinds one rank up).  ``boundary``
    is the per-axis (z, y, x) mode triple (see
    :func:`strip_launch_geometry`)."""
    z, h, n = x_shape
    bz, by, bx = resolve_boundary(boundary, 3)
    zs, sm = geom.z_slab, geom.strip_m
    gz, gm = z // zs, h // sm
    if geom.w_tile:
        zb, hb, wb = geom.z_block, geom.h_block, geom.w_block
        wt = geom.w_tile
        nbz, nby, nbw = zs // zb, sm // hb, wt // wb
        ring_y, ring_w = nby + 2, nbw + 2
        gw = -(-n // wt)
        aligned = n % wt == 0
        total_z, total_y = z // zb, h // hb
        if aligned:
            total_w = n // wb
            src_shape, out_w = (z, h, n), n

            def col_index(iw, jw):
                return _axis_block_index(iw * nbw + jw - 1, total_w, bx)
        else:
            src_shape = (z, h, gw * wt + 2 * wb)
            out_w = gw * wt

            def col_index(iw, jw):
                return iw * nbw + jw   # the extension carries the boundary

        def block_index(iz, iy, iw, j):
            jz = j // (ring_y * ring_w)
            jy = (j // ring_w) % ring_y
            jw = j % ring_w
            return (_axis_block_index(iz * nbz + jz - 1, total_z, bz),
                    _axis_block_index(iy * nby + jy - 1, total_y, by),
                    col_index(iw, jw))

        lg = LaunchGeometry(
            kind="slab_coltiled",
            grid=(gz, gm, gw, (nbz + 2) * ring_y * ring_w),
            in_block=(zb, hb, wb),
            in_index_map=block_index,
            out_block=(zs, sm, wt),
            out_index_map=lambda iz, iy, iw, j: (iz, iy, iw),
            out_shape=(z, h, out_w),
            src_shape=src_shape,
            halo=halo, x_halo=x_halo,
            scratch_shape=(zs + 2 * zb, sm + 2 * hb, wt + 2 * wb),
            ring_dims=(nbz + 2, ring_y, ring_w),
            block_dims=(zb, hb, wb),
            read_bounds=((zb - halo, zb + zs + halo),
                         (hb - halo, hb + sm + halo),
                         (wb - x_halo, wb + wt + x_halo)),
            aligned=aligned,
            boundary=(bz, by, bx),
        )
    else:
        zb, hb = geom.z_block, geom.h_block
        nbz, nby = zs // zb, sm // hb
        ring_y = nby + 2
        total_z, total_y = z // zb, h // hb

        def block_index(iz, iy, j):
            jz, jy = j // ring_y, j % ring_y
            return (_axis_block_index(iz * nbz + jz - 1, total_z, bz),
                    _axis_block_index(iy * nby + jy - 1, total_y, by), 0)

        lg = LaunchGeometry(
            kind="slab_subblocked", grid=(gz, gm, (nbz + 2) * ring_y),
            in_block=(zb, hb, n),
            in_index_map=block_index,
            out_block=(zs, sm, n),
            out_index_map=lambda iz, iy, j: (iz, iy, 0),
            out_shape=(z, h, n), src_shape=(z, h, n),
            halo=halo, x_halo=x_halo,
            scratch_shape=(zs + 2 * zb, sm + 2 * hb, n),
            ring_dims=(nbz + 2, ring_y), block_dims=(zb, hb),
            read_bounds=((zb - halo, zb + zs + halo),
                         (hb - halo, hb + sm + halo),
                         (0, n)),
            boundary=(bz, by, bx),
        )
    from repro.testing.faults import corrupt_geometry
    return corrupt_geometry(lg)


def lift_boundary_1d(boundary) -> tuple:
    """The (rows, cols) boundary of a 1D grid lifted through the 2D
    substrate: the synthetic unit row axis is periodic (it has no halo at
    all), the real axis keeps its mode."""
    (bx,) = resolve_boundary(boundary, 1)
    return ("periodic", bx)


def launch_geometry(grid_shape, geom: SubstrateGeom, halo: int,
                    x_halo: int = 0, boundary=None) -> LaunchGeometry:
    """The launch geometry the substrate would build for ``grid_shape``
    under ``geom``: rank dispatch matching the kernels exactly (1D grids
    lift to (1, N) with strip_m=1 and zero vertical halo)."""
    if geom.dim == 1 or len(grid_shape) == 1:
        return strip_launch_geometry(
            (1, grid_shape[-1]), 1, 0, 0,
            boundary=lift_boundary_1d(boundary))
    if len(grid_shape) == 2:
        return strip_launch_geometry(
            grid_shape, geom.strip_m, geom.h_block, halo,
            geom.w_tile, geom.w_block, x_halo, boundary=boundary)
    return slab_launch_geometry(grid_shape, geom, halo, x_halo,
                                boundary=boundary)


def _edge_flags(lg: LaunchGeometry):
    """Per-region-axis (is_lo, is_hi) domain-edge flags of the current
    grid cell, traced from ``pl.program_id`` -- called INSIDE the kernel
    body.  Cell axes are the leading grid axes (the ring, when present,
    is the last); region axes beyond the cell axes span the full extent
    in every cell (full-width x), so both of their flags are statically
    True.  Non-periodic kernels gate their boundary fills on these."""
    has_ring = lg.scratch_shape is not None
    cell_axes = len(lg.grid) - (1 if has_ring else 0)
    flags = []
    for ax in range(len(lg.out_block)):
        if ax < cell_axes:
            pid = pl.program_id(ax)
            flags.append((pid == 0, pid == lg.grid[ax] - 1))
        else:
            flags.append((True, True))
    return tuple(flags)


def _pin_region(cur: jax.Array, interpret: bool) -> jax.Array:
    """Interpret mode only: an ``optimization_barrier`` between region
    assembly and compute, so that interpret-mode FMA formation does not
    depend on the ring geometry.  The interpreter runs the kernel body
    on XLA:CPU, which would otherwise fuse the read of the assembled
    region (a scratch slice whose offsets follow h_block and z_block)
    into the tap sums and contract their FMAs differently per geometry
    -- two rings of one grid then differ in the last ulp, where the
    tests assert them bitwise equal.  Mosaic has no lowering for the
    barrier, so compiled kernels go without it."""
    return jax.lax.optimization_barrier(cur) if interpret else cur


def _launch(lg: LaunchGeometry, compute, x: jax.Array, interpret: bool,
            consts=(), name: str = None, scopes: bool = False) -> jax.Array:
    """Execute one launch geometry: THE place every substrate kind lowers
    through.  Grid, BlockSpecs, scratch, ring slots, fire step and read
    window all come from ``lg`` -- the kernel body only dispatches on
    whether a scratch exists (the ring) or not (the lifted 1D "flat"
    launch, whose one block is its whole region).

    ``compute(cur, edges, *const_refs)`` receives the f32 halo-extended
    region and the per-axis domain-edge flags (``None`` on all-periodic
    launches, where no fill can ever fire -- keeping the default jaxpr
    bit-identical to the historical substrate).

    ``name`` names the kernel in the compiled program and the profiler
    trace (the plan passes its backend's name).  ``scopes`` compiles two
    in-kernel trace scopes in (``repro.core.trace``), each a
    ``tpu.trace_start``/``trace_stop`` pair:

      * ``repro.substrate.assemble`` -- ring kinds: every grid step's
        store of its fetched block into its ring slot; "flat": the read
        of its block (and its cast);
      * ``repro.kernel.compute`` -- the fire step: the read of the
        assembled region, ``compute`` (every fused step's fills and tap
        sums or contractions) and the store to the output block.

    Outside both lies what the BlockSpec pipeline does around the body:
    DMA waits and per-grid-step overhead.  ``scopes=False`` traces no
    scope at all, so the kernel is byte-identical to an unscoped one."""
    out_dtype = x.dtype
    rank = len(lg.grid)
    zero_map = _ZERO_INDEX_MAPS[rank]
    in_specs = ([pl.BlockSpec(lg.in_block, lg.in_index_map)]
                + [pl.BlockSpec(c.shape, zero_map((0,) * c.ndim))
                   for c in consts])
    src = x
    if lg.src_shape != x.shape:
        # Edge-tile remainder path: boundary-extend + zero-pad the last
        # axis on the host so the non-wrapping column walk is in bounds
        # everywhere (DESIGN.md §10).
        src = _extend_columns_for_tiling(
            x, lg.block_dims[-1], lg.grid[-2], lg.out_block[-1],
            mode=lg.boundary[-1] if lg.boundary else "periodic")
    edged = lg.boundary and not lg.periodic

    if lg.scratch_shape is None:
        def kern(blk_ref, *rest):
            *const_refs, out_ref = rest
            edges = _edge_flags(lg) if edged else None
            with trace.scope(trace.SUBSTRATE_ASSEMBLE, scopes):
                cur = _pin_region(blk_ref[...].astype(jnp.float32),
                                  interpret)
            with trace.scope(trace.KERNEL_COMPUTE, scopes):
                out_ref[...] = compute(cur, edges,
                                       *const_refs).astype(out_dtype)

        extra = {}
    else:
        full = (slice(None),) * (len(lg.scratch_shape) - len(lg.block_dims))
        read_ix = tuple(slice(lo, hi) for lo, hi in lg.read_bounds)
        ring_axis = rank - 1
        fire = lg.fire_step

        def kern(blk_ref, *rest):
            *const_refs, out_ref, scratch_ref = rest
            j = pl.program_id(ring_axis)
            slot = tuple(pl.ds(s, b) for s, b in lg.scratch_slot(j))
            with trace.scope(trace.SUBSTRATE_ASSEMBLE, scopes):
                scratch_ref[slot + full] = blk_ref[...]
            # program_id must be read at kernel top level: the interpret
            # path only substitutes it outside pl.when bodies.
            edges = _edge_flags(lg) if edged else None

            @pl.when(j == fire)
            def _compute():
                with trace.scope(trace.KERNEL_COMPUTE, scopes):
                    cur = _pin_region(
                        scratch_ref[read_ix].astype(jnp.float32), interpret)
                    out_ref[...] = compute(cur, edges,
                                           *const_refs).astype(out_dtype)

        extra = {"scratch_shapes": [pltpu.VMEM(lg.scratch_shape, x.dtype)]}

    y = pl.pallas_call(
        kern,
        grid=lg.grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec(lg.out_block, lg.out_index_map),
        out_shape=jax.ShapeDtypeStruct(lg.out_shape, x.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        name=name,
        **extra,
    )(src, *consts)
    if lg.out_shape != x.shape:
        y = y[..., : x.shape[-1]]
    return y


def strip_substrate_call(compute, x: jax.Array, strip_m: int, h_block: int,
                         halo: int, interpret: bool, consts=(),
                         w_tile: int = 0, w_block: int = 0,
                         x_halo: int = 0, boundary=None, name: str = None,
                         scopes: bool = False) -> jax.Array:
    """Launch ``compute`` over every output strip of a 2D (or lifted 1D)
    grid.

    The ONE place the strip kernels lower through -- substrate changes
    (semantics, buffering) happen here, never per kernel.
    ``compute(cur, edges, *const_refs)`` receives the f32 halo-extended
    region, the per-axis domain-edge flags (``None`` on all-periodic
    launches) plus one VMEM ref per ``consts`` operand (operands
    constant across the grid, e.g. banded weights) and returns the
    output region; the launcher casts back to ``x.dtype``.  ``boundary``
    is the per-axis mode pair threaded into the launch geometry
    (DESIGN.md §15).  The launch is the (strip, h-block) ring with VMEM
    scratch assembly (module docstring); ``halo=0`` (the lifted-1D case:
    no vertical support at all) drops the neighbor loads entirely --
    each strip streams only its own rows, read amplification exactly 1.

    Full width (``w_tile=0``): ``compute`` maps (strip_m + 2*halo, n) ->
    (strip_m, n) and re-wraps the x-halo in-VMEM itself (every row is a
    complete global row).  Column-tiled (``w_tile`` > 0, DESIGN.md §10):
    the grid gains a w-tile dimension and the walk covers the full
    (h_block, w_block) block ring; ``compute`` maps
    (strip_m + 2*halo, w_tile + 2*x_halo) -> (strip_m, w_tile) and must
    CARRY the ``x_halo``-deep x support (scratch rows are partial, so no
    re-wrap is possible).  Widths not divisible by ``w_tile`` run the
    edge-tile remainder path: the input is periodically extended by one
    w_block per side on the host, the column walk drops its modulo wrap,
    and the padded output columns are sliced off.
    """
    # Fault-injection hooks (repro.testing.faults): each plan traces its
    # jitted runner exactly once, so a hook here models "the Nth kernel
    # compile fails" / "the VMEM estimate lied".  No-ops unless armed.
    from repro.testing.faults import maybe_fail
    maybe_fail("compile")
    maybe_fail("vmem")

    lg = strip_launch_geometry(x.shape, strip_m, h_block, halo,
                               w_tile, w_block, x_halo, boundary=boundary)
    return _launch(lg, compute, x, interpret, consts, name, scopes)


def _extend_columns_for_tiling(x: jax.Array, w_block: int, gw: int,
                               w_tile: int,
                               mode: str = "periodic") -> jax.Array:
    """Edge-tile remainder path's host-side input: boundary-extend the
    last axis by one w_block per side (so the non-wrapping column walk
    still finds halo columns at both grid edges), then zero-pad on the
    right up to ``gw * w_tile + 2 * w_block`` columns so every fetched
    block is in bounds.  The pad region is only ever read by output
    columns beyond W, which the launcher slices off.

    ``mode`` generalizes the historical "periodic host extension" to a
    boundary host extension (DESIGN.md §15): non-periodic modes extend
    with their pad values -- though step-1 values are all the extension
    could supply, and the kernels re-impose the boundary in kernel at
    EVERY fused step anyway, so the non-periodic extension only has to
    be finite and in-bounds.
    """
    n = x.shape[-1]
    if mode == "periodic":
        ext = jnp.concatenate([x[..., -w_block:], x, x[..., :w_block]],
                              axis=-1)
    else:
        pad = [(0, 0)] * x.ndim
        pad[-1] = (w_block, w_block)
        ext = jnp.pad(x, pad, mode=PAD_MODE[mode])
    pad_cols = gw * w_tile - n
    if pad_cols:
        pad = [(0, 0)] * x.ndim
        pad[-1] = (0, pad_cols)
        ext = jnp.pad(ext, pad)
    return ext


def slab_substrate_call(compute, x: jax.Array, geom: SubstrateGeom,
                        halo: int, interpret: bool, consts=(),
                        x_halo: int = 0, boundary=None, name: str = None,
                        scopes: bool = False) -> jax.Array:
    """Launch ``compute`` over every (z-slab, strip) output cell of a 3D
    grid (module docstring, DESIGN.md §9).

    The 3D analogue of ``strip_substrate_call`` -- and like it, the ONE
    place the 3D kernels lower through.
    ``compute(cur, edges, *const_refs)``
    receives the (z_slab + 2*halo, strip_m + 2*halo, W) f32 halo-extended
    slab (periodic in z and y via the modulo index maps; the x-wrap is the
    kernels' own in-VMEM job) and returns the (z_slab, strip_m, W) output
    slab.  ONE (z_block, h_block, W) input reference walks, for output
    cell (iz, iy), the (z_slab/z_block + 2) x (strip_m/h_block + 2) block
    ring -- own blocks plus the single neighbor blocks that can contain
    halo planes/rows -- into a VMEM scratch of
    (z_slab + 2*z_block, strip_m + 2*h_block, W); compute fires on the
    ring's final block (``pl.when``).  Every ring of one grid assembles
    byte-identical extended slabs, so (with ``_pin_region`` between
    assembly and compute in interpret mode) their outputs are
    bit-for-bit equal.

    ``geom.w_tile`` > 0 selects the column-tiled scheme (DESIGN.md §10):
    the grid gains a w-tile dimension, the input reference shrinks to
    (z_block, h_block, w_block) walking the full 3-axis block ring, and
    ``compute`` maps (z_slab + 2*halo, strip_m + 2*halo,
    w_tile + 2*x_halo) -> (z_slab, strip_m, w_tile), CARRYING the x-halo
    instead of re-wrapping (scratch rows are partial).  Widths not
    divisible by w_tile run the host-extended edge-tile remainder path.
    """
    # Same fault-injection hooks as strip_substrate_call (trace-time).
    from repro.testing.faults import maybe_fail
    maybe_fail("compile")
    maybe_fail("vmem")

    lg = slab_launch_geometry(x.shape, geom, halo, x_halo,
                              boundary=boundary)
    return _launch(lg, compute, x, interpret, consts, name, scopes)


def fold_batch(run, mode: str):
    """Fold a leading batch axis through a single-grid runner (DESIGN.md
    §12): the returned callable consumes ``(B,) + grid_shape`` and is
    bitwise-equal to stacking ``B`` calls of ``run``.

    ``mode="vmap"`` batches the kernels themselves -- Pallas's batching
    rule prepends a batch grid dimension, so one launch covers the whole
    bucket (the right shape on real hardware, where the extra grid
    dimension is free).  ``mode="map"`` scans ``run`` over the batch
    inside one jitted computation -- per-request VMEM working set and
    numerics are IDENTICAL to the unbatched plan, and the host dispatch +
    sync cost is paid once per bucket instead of once per request (the
    right shape under interpret mode, where emulated kernels make Python
    dispatch the bottleneck).  The serving engine picks via the plan's
    ``batch_mode`` ("auto" resolves per DESIGN.md §12).
    """
    if mode == "vmap":
        return jax.vmap(run)
    if mode == "map":
        return lambda xb: jax.lax.map(run, xb)
    raise ValueError(f"fold_batch mode must be 'vmap' or 'map', got {mode!r}")


def substrate_read_amp(strip_m: int, h_block: int) -> float:
    """Analytic grid-read amplification of one ring axis.

    Each output strip streams its own rows once plus one h-block of each
    vertical neighbor -> 1 + 2*h_block/strip_m (3.0 at h_block =
    strip_m, three full strips).  ``None`` is rejected: everywhere else
    in the kernel API it means "auto", which this function cannot
    resolve (it has no halo) -- resolve first (``choose_hblock``); 0
    names no ring.
    """
    if not h_block:
        raise ValueError(f"h_block={h_block!r} names no block ring; 'auto' "
                         "(None) must be resolved via choose_hblock first")
    return 1.0 + 2.0 * h_block / strip_m


def resolve_strip_blocks(grid_shape, halo: int, dtype_bytes: int,
                         tile_m: int = None, h_block: int = None,
                         w_tile: int = None, w_block: int = None,
                         x_halo: int = None) -> tuple:
    """Resolve (strip_m, h_block, w_tile, w_block) from possibly-``None``
    user requests.

    The 2D slice of the shared sizing rule -- ``resolve_substrate_geom``
    delegates its dim-2 branch here, so plan-level and kernel-level sizing
    can never drift apart.  ``tile_m=None`` sizes both jointly
    (``choose_strip_blocks``); an explicit ``tile_m`` is clamped to the
    grid and, when ``h_block`` is also ``None``, gets ``choose_hblock``
    of the clamped strip.

    Width (DESIGN.md §10): full width (w_tile=0) whenever pinned so or
    the full-width working set fits the VMEM budget; otherwise the
    column-tiled joint sizing ``choose_col_blocks`` runs, conditioned on
    any strip/width pins.
    """
    h, wid = grid_shape
    xh = halo if x_halo is None else x_halo
    w_tile, w_block = _normalize_w_pin(w_tile, w_block, wid)
    if w_block and w_tile is None:
        # Uniform lone-pin rejection: a w_block without a w_tile names no
        # substrate on EITHER resolution path -- acceptance must not flip
        # with the VMEM budget (the auto w_tile need not be divisible).
        _resolve_w_block(0, w_block, xh)
    budget = vmem_budget_bytes()
    sub = sublane_tile(dtype_bytes)

    def fullwidth() -> tuple:
        if tile_m is None:
            strip_m, auto_hb = choose_strip_blocks(h, wid, halo, dtype_bytes,
                                                   budget)
        else:
            strip_m, auto_hb = min(tile_m, h), None
        hb = h_block
        if hb is None:
            hb = choose_hblock(strip_m, halo, sub) if auto_hb is None \
                else auto_hb
        return strip_m, hb

    if w_tile == 0:
        sm, hb = fullwidth()
        _resolve_w_block(0, w_block, xh)    # rejects a lone w_block pin
        return sm, hb, 0, 0
    if w_tile is None:
        sm, hb = fullwidth()
        if _strip_working_set(sm, hb, wid, halo, dtype_bytes) <= budget:
            return sm, hb, 0, 0
    sm, auto_hb, wt, auto_wb = choose_col_blocks(
        h, wid, halo, xh, dtype_bytes, budget,
        m_pin=min(tile_m, h) if tile_m is not None else None,
        w_pin=w_tile)
    hb = h_block if h_block is not None else auto_hb
    wt, wb = _resolve_w_block(wt, w_block if w_block else auto_wb, xh)
    return sm, hb, wt, wb


def hbm_read_bytes_per_step(shape, strip_m: int, dtype_bytes: int,
                            bands_shape=None, *, h_block: int,
                            w_tile: int = 0, w_block: int = 0) -> int:
    """Analytic HBM read traffic of one strip-substrate kernel launch.

    Each output strip streams ``strip_m/h_block + 2`` (h_block, n) blocks
    -> the grid is read ``1 + 2*h_block/strip_m`` times (3x at h_block =
    strip_m; this analytic model has no halo to auto-resolve from, so
    ``h_block`` is required, as in ``substrate_read_amp``).  Column-tiled
    (``w_tile`` > 0, DESIGN.md §10): each of the
    ``(h/strip_m)(ceil(w/w_tile))`` output
    tiles streams its (strip_m + 2*h_block, w_tile + 2*w_block) block
    neighborhood -> the product amplification
    (1 + 2*h_block/strip_m)(1 + 2*w_block/w_tile) on aligned widths
    (the remainder path adds one partial tile column plus the one-off
    host extension, which is not per-step traffic).  The banded operand
    (if any) is charged once per output cell (its block index is
    constant within a cell's revisit chain).
    """
    import numpy as np

    h, w = shape
    gm = h // strip_m
    # One formula per axis: substrate_read_amp is the model (and rejects
    # the h_block=None 'auto' sentinel); rows = strip_m + 2*h_block exactly.
    rows_per_strip = round(strip_m * substrate_read_amp(strip_m, h_block))
    if w_tile:
        gw = -(-w // w_tile)
        cols_per_tile = round(w_tile * substrate_read_amp(w_tile, w_block))
        cells = gm * gw
        total = cells * rows_per_strip * cols_per_tile * dtype_bytes
    else:
        cells = gm
        total = gm * rows_per_strip * w * dtype_bytes
    if bands_shape is not None:
        total += cells * int(np.prod(bands_shape)) * dtype_bytes
    return total


def hbm_read_bytes_per_step_3d(shape, geom: SubstrateGeom, dtype_bytes: int,
                               bands_shape=None) -> int:
    """Analytic HBM read traffic of one 3D slab-substrate kernel launch.

    Each of the (Z/z_slab)(H/strip_m) cells streams the
    (z_slab + 2*z_block)(strip_m + 2*h_block) block ring -> the grid is
    read (1 + 2*h_block/strip_m)(1 + 2*z_block/z_slab) times (9x at
    z_block = z_slab, h_block = strip_m).
    Column-tiled (``geom.w_tile`` > 0): the x axis joins the ring and
    the amplification gains the (1 + 2*w_block/w_tile) factor
    (DESIGN.md §10).  The banded operand (if any) is charged once per
    output cell, as in 2D.
    """
    import numpy as np

    z, h, w = shape
    if geom.dim != 3:
        raise ValueError(f"3D traffic model needs a 3D geometry, got {geom}")
    cells = (z // geom.z_slab) * (h // geom.strip_m)
    planes = round(geom.z_slab
                   * substrate_read_amp(geom.z_slab, geom.z_block))
    rows = round(geom.strip_m
                 * substrate_read_amp(geom.strip_m, geom.h_block))
    if geom.w_tile:
        gw = -(-w // geom.w_tile)
        cols = round(geom.w_tile
                     * substrate_read_amp(geom.w_tile, geom.w_block))
        cells *= gw
        total = cells * planes * rows * cols * dtype_bytes
    else:
        total = cells * planes * rows * w * dtype_bytes
    if bands_shape is not None:
        total += cells * int(np.prod(bands_shape)) * dtype_bytes
    return total
