"""Scratch dependence checker: prove the VMEM ring assembly correct.

The sub-blocked substrates ((strip, ring), (strip, w-tile, ring) and
(z-slab, strip, w-tile, ring) -- DESIGN.md §3/§9/§10) stream halo blocks
into a VMEM scratch over the last grid axis and fire compute on the final
ring step.  This module verifies, statically and per launch geometry:

  * ``scratch/slots-partition`` -- the ring's write slots are pairwise
    disjoint and exactly tile the scratch's ringed extents (every slot
    written once per cell, no conflicting overlapping writes);
  * ``scratch/read-window``     -- the compute read window lies inside
    the scratch and spans exactly the output tile plus its halos
    (leading axes: 2*halo; carried-x axis: 2*x_halo), i.e. full halo
    coverage with nothing unwritten;
  * ``scratch/fire-last``       -- compute fires on the LAST ring step,
    after every slot of the cell's ring has been written (the grid walks
    the ring axis fastest, so steps 0..ring-1 of a cell are consecutive);
  * ``scratch/gather-window``   -- sparse-compacted launches (engine
    ``"sparse_matmul"``, DESIGN.md §14) declare per-band gather metadata
    (``band_lo``, ``band_spans``); every band's gathered input window
    ``[lo, lo + tile + span)`` must lie inside the dense band support
    (``0 <= lo``, ``lo + span <= 2*radius``) and the packed operand's
    row count must equal ``sum(tile_n + span_p)`` -- full coverage of
    the kept contraction rows with nothing read out of bounds;
  * ``scratch/coverage-global`` -- for each sampled output cell and every
    ring step, the fetched source block lands in the slot whose scratch
    coordinates correspond to its true global coordinates: scratch
    position ``p`` on ringed axis ``ax`` must hold global index
    ``cell*tile + (p - block)`` (periodic), which pins slot ``k`` to
    global start ``(cell*tile + (k-1)*block) mod extent`` on aligned
    axes and to extended-source start ``cell*tile + k*block`` on the
    remainder path's non-wrapping column axis.  This is the PR 5 class
    of halo off-by-ones the auditor exists to catch.
"""
from __future__ import annotations

import itertools
import math
from typing import List

from .report import AuditCheck


def _sample_cells(cell_dims, limit: int = 64):
    """All cells when few; otherwise the corner/mid lattice per axis
    (the index maps are affine-with-modulo per axis, so corners + an
    interior point witness every residue behavior)."""
    if math.prod(cell_dims) <= limit:
        return list(itertools.product(*map(range, cell_dims)))
    axes = []
    for d in cell_dims:
        pts = sorted({0, d // 2, d - 1})
        axes.append(pts)
    return list(itertools.product(*axes))


def _gather_window_check(launch) -> AuditCheck:
    """Sparse-compacted launches: prove the per-band gather metadata
    covers exactly the kept contraction rows inside the dense band
    support (the compacted analogue of ``scratch/read-window``)."""
    r, tile_n = launch.radius, launch.tile_n
    lo, spans = launch.band_lo, launch.band_spans
    problems = []
    if lo is None or spans is None:
        problems.append("missing band_lo/band_spans metadata")
    elif not (len(lo) == len(spans) == launch.n_offsets):
        problems.append(f"{len(lo)} band_lo / {len(spans)} band_spans "
                        f"!= {launch.n_offsets} offsets")
    else:
        for p, (l, s) in enumerate(zip(lo, spans)):
            if not (0 <= l and 0 <= s and l + s <= 2 * r):
                problems.append(f"band {p}: window [lo={l}, lo+span={l+s}) "
                                f"outside dense support [0, {2*r}]")
        kept = sum(tile_n + s for s in spans)
        if launch.bands_shape is None or kept != launch.bands_shape[0]:
            problems.append(f"packed rows {launch.bands_shape} != "
                            f"sum(tile_n + span) = {kept}")
    return AuditCheck(
        "scratch/gather-window", not problems,
        expected="every band gathers [lo, lo + tile + span) inside the "
                 "dense band support; packed rows == sum(tile_n + span)",
        actual=problems or "ok",
        detail="sparse-compacted gather metadata must cover exactly the "
               "kept contraction rows (DESIGN.md §14)")


def audit_scratch(lg, launch) -> List[AuditCheck]:
    """All scratch-pipeline checks for one launch geometry (empty list
    for the scratch-free "flat" kind -- nothing to prove beyond the
    sparse gather window, which is substrate-independent)."""
    checks: List[AuditCheck] = []
    if launch.engine == "sparse_matmul":
        checks.append(_gather_window_check(launch))
    if lg.scratch_shape is None:
        return checks
    ring = lg.ring
    n_ring_axes = len(lg.block_dims)

    # ---- write slots partition the ringed scratch extents -------------
    slots = [lg.scratch_slot(j) for j in range(ring)]
    distinct = len(set(slots)) == ring
    in_extent = all(
        start >= 0 and start + size <= lg.scratch_shape[ax]
        for slot in slots for ax, (start, size) in enumerate(slot))
    exact_tile = all(
        lg.ring_dims[ax] * lg.block_dims[ax] == lg.scratch_shape[ax]
        for ax in range(n_ring_axes))
    checks.append(AuditCheck(
        "scratch/slots-partition", distinct and in_extent and exact_tile,
        expected={"distinct_slots": ring, "exact_tiling": True},
        actual={"distinct_slots": len(set(slots)),
                "in_extent": in_extent, "exact_tiling": exact_tile},
        detail="ring write slots must be disjoint and tile the scratch"))

    # ---- read window: inside the scratch, spanning tile + halos -------
    expected_spans = []
    for ax in range(len(lg.scratch_shape)):
        tile = lg.out_block[ax]
        if ax < n_ring_axes:
            is_w = ax == len(lg.scratch_shape) - 1
            tile += 2 * (lg.x_halo if is_w else lg.halo)
        expected_spans.append(tile)
    window_ok = len(lg.read_bounds) == len(lg.scratch_shape)
    spans = []
    if window_ok:
        for ax, (lo, hi) in enumerate(lg.read_bounds):
            window_ok &= 0 <= lo <= hi <= lg.scratch_shape[ax]
            spans.append(hi - lo)
        window_ok &= spans == expected_spans
    checks.append(AuditCheck(
        "scratch/read-window", window_ok,
        expected=expected_spans,
        actual=spans,
        detail="compute must read exactly the output tile + halo from "
               "inside the scratch"))

    # ---- compute fires on the final ring step -------------------------
    fire_ok = (lg.fire_step == ring - 1
               and lg.ring_indices(lg.fire_step)
               == tuple(d - 1 for d in lg.ring_dims))
    checks.append(AuditCheck(
        "scratch/fire-last", fire_ok,
        expected=ring - 1, actual=lg.fire_step,
        detail="compute may only fire once every slot is written"))

    # ---- global-coordinate coverage per sampled cell ------------------
    from repro.kernels.common import _reflect_block
    cell_dims = lg.grid[:-1]
    modes = lg.boundary or ()
    bad = []
    for cell in _sample_cells(cell_dims):
        for j in range(ring):
            idx = lg.in_index_map(*cell, j)
            ks = lg.ring_indices(j)
            for ax in range(n_ring_axes):
                b = lg.block_dims[ax]
                tile = lg.out_block[ax]
                actual = idx[ax] * b
                # Cell-grid axes list the ringed source axes 1:1 in
                # order for every scratch kind (subblocked, coltiled
                # and their slab lifts), so cell[ax] feeds ring axis ax
                # -- and lg.boundary[ax] names its mode (DESIGN.md §15).
                mode = modes[ax] if ax < len(modes) else "periodic"
                last_unaligned = (ax == n_ring_axes - 1
                                  and not lg.aligned)
                if last_unaligned:
                    # Remainder path: non-wrapping walk over the
                    # host-extended source, shifted one block right
                    # (the extension carries the boundary, any mode).
                    expect = cell[ax] * tile + ks[ax] * b
                    ok = actual == expect
                elif mode == "periodic":
                    extent = lg.src_shape[ax]
                    expect = (cell[ax] * tile + (ks[ax] - 1) * b) % extent
                    ok = actual % extent == expect
                else:
                    # Non-periodic axes must REFLECT out-of-range block
                    # indices (never wrap, never revisit a block on
                    # consecutive ring steps): the exact in-bounds start
                    # the kernels' in-kernel fills assume.
                    total = lg.src_shape[ax] // b
                    expect = _reflect_block(
                        cell[ax] * (tile // b) + ks[ax] - 1, total) * b
                    ok = actual == expect
                if not ok and len(bad) < 8:
                    bad.append({"cell": cell, "ring_step": j, "axis": ax,
                                "mode": mode,
                                "expected_start": expect,
                                "actual_start": actual})
    checks.append(AuditCheck(
        "scratch/coverage-global", not bad,
        expected="every slot holds its true global halo block",
        actual=bad or "ok",
        detail="scratch slot k on axis ax must hold global rows "
               "(cell*tile + (k-1)*block) mod extent on periodic axes, "
               "reflect_block(cell*nb + k - 1) * block on non-periodic "
               "axes (DESIGN.md §15)"))
    return checks
