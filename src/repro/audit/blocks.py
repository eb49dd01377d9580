"""Block-access auditor: enumerate every BlockSpec index map statically.

A Pallas launch's HBM read traffic is fully determined by its
:class:`repro.kernels.common.LaunchGeometry`: the index maps are pure
Python closures over ints, so calling them with every concrete grid index
-- no tracing, no execution -- yields the exact sequence of block indices
each input reference visits.  Pallas's pipeline only refetches a block
when the index CHANGES between consecutive grid steps (the revisit
optimization), so the audited traffic applies consecutive deduplication
per reference; on every non-degenerate substrate geometry the walk never
revisits consecutively and the deduplicated count equals the analytic
step count exactly.

Checks emitted per launch:

  * ``blocks/in-bounds``     -- every fetched block lies inside the
    (possibly host-extended) source array; every written output block
    inside the launch output.
  * ``blocks/out-cover``     -- the output blocks tile ``out_shape``
    exactly once, and the out index map is constant across the ring.
  * ``blocks/grid-bytes-model`` -- deduplicated grid-input bytes/step ==
    ``hbm_read_bytes_per_step{,_3d}`` (grid term), exact integer
    equality.
  * ``blocks/read-amp-geom`` -- audited bytes / (padded output bytes) ==
    ``SubstrateGeom.read_amp`` (rtol 1e-9; the padded output absorbs the
    remainder path's edge tile exactly as the model does).
  * ``blocks/bands-term``    -- for MXU launches, the model's banded
    operand term charges exactly ``cells * prod(bands_shape) * D``.
  * ``blocks/grid-steps-model`` -- ``SubstrateGeom.grid_steps``, the
    step count the sizing priced, == the launch grid's steps.
"""
from __future__ import annotations

import itertools
import math
from typing import List

from .report import AuditCheck

#: Exact enumeration cap: grids whose launch exceeds this many grid steps
#: skip the byte-level checks (recorded as skipped, never violations).
#: Substrate grids are block-granular, so realistic launches are far
#: below it; the cap only guards pathological plan-attached audits.
MAX_GRID_STEPS = 2_000_000


def enumerate_fetches(lg):
    """Walk the full launch grid; return the input block fetch count
    under Pallas revisit semantics (a block index equal to the previous
    step's is not fetched again) plus the raw step count.

    Returns ``(fetches, n_steps)``.
    """
    grid = lg.grid
    fetches, prev = 0, None
    for ix in itertools.product(*map(range, grid)):
        idx = lg.in_index_map(*ix)
        if idx != prev:
            fetches += 1
            prev = idx
    return fetches, math.prod(grid)


def _block_limits(shape, block):
    """Max valid block index per axis (our launches never use partial
    edge blocks: the remainder path pads the source instead)."""
    return tuple(s // b for s, b in zip(shape, block))


def _degenerate_axes(lg):
    """Ringed axes whose modulo-wrapped block walk aliases consecutively
    (total extent 1 block).  The analytic model charges such axes as if
    every step fetched; Pallas's revisit optimization would not.  The
    byte comparison is skipped there -- the model is conservative."""
    out = []
    for ax, b in enumerate(lg.block_dims):
        if ax == len(lg.block_dims) - 1 and not lg.aligned:
            continue            # remainder walk never wraps: no aliasing
        if lg.src_shape[ax] // b == 1:
            out.append(ax)
    return out


def audit_blocks(lg, launch, dtype_bytes: int) -> List[AuditCheck]:
    """All block-access checks for one launch geometry.

    ``launch`` is the registry's :class:`LaunchAudit` (engine, geometry,
    bands shape); ``lg`` the :class:`LaunchGeometry` it launches.
    """
    checks: List[AuditCheck] = []
    n_steps = math.prod(lg.grid)
    checks.append(AuditCheck(
        "blocks/grid-steps-model", launch.geom.grid_steps == n_steps,
        expected=n_steps, actual=launch.geom.grid_steps,
        detail="SubstrateGeom.grid_steps vs the launch grid's steps"))
    if n_steps > MAX_GRID_STEPS:
        checks.append(AuditCheck(
            "blocks/grid-bytes-model", True, skipped=True,
            detail=f"grid has {n_steps} steps > {MAX_GRID_STEPS}; exact "
                   "enumeration skipped"))
        return checks

    # ---- enumerate, checking bounds and output coverage as we walk ----
    in_lim = _block_limits(lg.src_shape, lg.in_block)
    out_lim = _block_limits(lg.out_shape, lg.out_block)
    oob = []
    out_blocks = {}
    ring_drift = []
    fetches, prev = 0, None
    for ix in itertools.product(*map(range, lg.grid)):
        idx = lg.in_index_map(*ix)
        if any(not 0 <= b < lim for b, lim in zip(idx, in_lim)):
            if len(oob) < 8:
                oob.append((ix, "in", idx))
        if idx != prev:
            fetches += 1
            prev = idx
        oidx = lg.out_index_map(*ix)
        if any(not 0 <= b < lim for b, lim in zip(oidx, out_lim)):
            if len(oob) < 8:
                oob.append((ix, "out", oidx))
        cell = ix[:-1] if lg.ring_dims else ix
        seen = out_blocks.setdefault(cell, oidx)
        if seen != oidx and len(ring_drift) < 8:
            ring_drift.append((ix, seen, oidx))

    checks.append(AuditCheck(
        "blocks/in-bounds", not oob, expected="all blocks in bounds",
        actual=oob or "ok",
        detail="" if not oob else "block index escapes the source array"))

    n_out_blocks = math.prod(
        s // b for s, b in zip(lg.out_shape, lg.out_block))
    cover_ok = (not ring_drift
                and len(out_blocks) == lg.cells == n_out_blocks
                and len(set(out_blocks.values())) == n_out_blocks)
    checks.append(AuditCheck(
        "blocks/out-cover", cover_ok,
        expected={"cells": lg.cells, "distinct_out_blocks": n_out_blocks},
        actual={"cells_seen": len(out_blocks),
                "distinct": len(set(out_blocks.values())),
                "ring_drift": ring_drift or "none"},
        detail="output blocks must tile out_shape exactly once, "
               "constant across the ring"))

    # ---- deduplicated grid bytes vs the analytic traffic model --------
    audited = fetches * math.prod(lg.in_block) * dtype_bytes
    model = _model_grid_bytes(launch, dtype_bytes)
    degenerate = _degenerate_axes(lg)
    if degenerate:
        checks.append(AuditCheck(
            "blocks/grid-bytes-model", True, skipped=True,
            expected=model, actual=audited,
            detail=f"ringed axes {degenerate} hold a single block: the "
                   "revisit optimization dedups what the model charges "
                   "(model is conservative)"))
    else:
        checks.append(AuditCheck(
            "blocks/grid-bytes-model", audited == model,
            expected=model, actual=audited,
            detail="dedup'd BlockSpec walk vs hbm_read_bytes_per_step "
                   "grid term"))

        out_bytes = math.prod(lg.out_shape) * dtype_bytes
        audited_amp = audited / out_bytes
        model_amp = launch.geom.read_amp
        checks.append(AuditCheck(
            "blocks/read-amp-geom",
            math.isclose(audited_amp, model_amp, rel_tol=1e-9),
            expected=model_amp, actual=audited_amp,
            detail="audited bytes / padded-output bytes vs "
                   "SubstrateGeom.read_amp"))

    # ---- banded operand term (MXU launches) ---------------------------
    if launch.bands_shape is not None:
        with_bands = _model_grid_bytes(launch, dtype_bytes,
                                       bands_shape=launch.bands_shape)
        expected_term = lg.cells * math.prod(launch.bands_shape) \
            * dtype_bytes
        checks.append(AuditCheck(
            "blocks/bands-term", with_bands - model == expected_term,
            expected=expected_term, actual=with_bands - model,
            detail="model must charge the banded operand once per output "
                   "cell at its actual built shape"))
    return checks


def _model_grid_bytes(launch, dtype_bytes: int, bands_shape=None) -> int:
    """The analytic model's read traffic for this launch's geometry."""
    from repro.kernels.common import (hbm_read_bytes_per_step,
                                      hbm_read_bytes_per_step_3d)
    geom = launch.geom
    shape = launch.grid_shape
    if geom.dim == 1 or len(shape) == 1:
        # Lifted 1D streams each point exactly once (read amp 1 --
        # DESIGN.md §9); the 2D formula does not apply to the lift.
        total = math.prod(shape) * dtype_bytes
        if bands_shape is not None:
            total += int(math.prod(bands_shape)) * dtype_bytes
        return total
    if len(shape) == 3:
        return hbm_read_bytes_per_step_3d(shape, geom, dtype_bytes,
                                          bands_shape=bands_shape)
    return hbm_read_bytes_per_step(shape, geom.strip_m, dtype_bytes,
                                   bands_shape=bands_shape,
                                   h_block=geom.h_block,
                                   w_tile=geom.w_tile,
                                   w_block=geom.w_block)


def audited_read_amp(lg, dtype_bytes: int) -> float:
    """Audited read amplification of one launch: dedup'd grid-input bytes
    over padded-output bytes (the third witness of the explain==decision
    parity sweep -- tests/test_audit.py)."""
    fetches, _ = enumerate_fetches(lg)
    audited = fetches * math.prod(lg.in_block) * dtype_bytes
    return audited / (math.prod(lg.out_shape) * dtype_bytes)
