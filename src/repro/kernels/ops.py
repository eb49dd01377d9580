"""Compatibility entry points over the plan API (repro.kernels.plan).

``stencil_apply(x, weights, t, backend="auto")`` is the historical one-shot
form: it builds-or-fetches a :class:`~repro.kernels.plan.StencilPlan` for
the call signature and executes it.  Selection, strip/tile sizing, and
weight preprocessing therefore run once per DISTINCT signature and are
served from the plan cache afterwards -- serving-scale callers should hold
a plan directly (``stencil_plan``) instead.

Backends (see ``repro.kernels.registry``; any registered name is accepted)
  direct              t sequential VPU kernel steps      (halo r per step)
  fused_direct        one VPU kernel, t in-VMEM steps     (paper's temporal fusion)
  matmul              t sequential MXU banded contractions (halo r per step)
  fused_matmul        weights composed to radius t*r, one  (paper's monolithic
                      MXU banded contraction                kernel fusion, alpha>1)
  fused_matmul_reuse  one MXU kernel, t radius-r banded    (intermediate reuse:
                      contractions w/ VMEM intermediates    alpha=1, halo-recompute
                                                            beta -- DESIGN.md §4)
  sparse_matmul /     the two regimes above with the banded (sparse-tensor-core
  fused_sparse_matmul operand compacted to its nonzero band regime; priced only
                      rows -- K shrinks by the kept-row     under use_sparse_unit
                      fraction S, bitwise-equal outputs     -- DESIGN.md §14)
  reference           jnp oracle (debug)
  auto                selector decides among the priced backends

Every Pallas backend runs on the one block-ring substrate; geometry pins
(``tile_m``, ``h_block``, ...) choose its read pattern, not the backend.

``interpret`` defaults to True off-TPU so every path is CPU-checkable; on a
real TPU it compiles through Mosaic.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.core import perfmodel as pm
from repro.core.selector import Decision
from . import registry
from .plan import decide, spec_from_weights, stencil_plan

def __getattr__(name):
    # BACKENDS is computed on access so late-registered plug-in backends
    # are visible: registered names + the "auto" selection policy.
    if name == "BACKENDS":
        return registry.registered_backends() + ("auto",)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


def stencil_apply(
    x: jax.Array,
    weights,
    t: int = 1,
    backend: str = "auto",
    hw: pm.HardwareSpec = pm.TPU_V5E_BF16,
    tile_m: Optional[int] = None,
    tile_n: Optional[int] = None,
    h_block: Optional[int] = None,
    z_slab: Optional[int] = None,
    z_block: Optional[int] = None,
    w_tile: Optional[int] = None,
    w_block: Optional[int] = None,
    interpret: Optional[bool] = None,
    compute_dtype=None,
    use_sparse_unit: bool = False,
    guard: bool = False,
    watchdog: Optional[bool] = None,
    boundary=None,
) -> jax.Array:
    """Advance the grid ``t`` time steps with the selected backend.

    Thin wrapper: equivalent to building ``stencil_plan(weights, x.shape,
    x.dtype, t, ...)`` and calling it -- identical signatures share one
    cached plan.  1D, 2D and 3D grids are supported (the grid rank must
    match ``weights.ndim``).  ``tile_m``/``tile_n``/``z_slab``/``w_tile``
    default to ``None`` = auto-sized by the kernels
    (``resolve_substrate_geom`` / ``choose_tile``; ``w_tile`` stays full
    width unless the full-width working set exceeds the VMEM budget --
    DESIGN.md §10); explicit values are validated strictly.

    ``guard=True`` routes through the guarded execution layer
    (``repro.kernels.guard``, DESIGN.md §11): kernel failures degrade
    down the fallback ladder instead of raising, and ``watchdog``
    (None = the ``REPRO_NAN_WATCHDOG`` env flag) arms the NaN/Inf check
    with a checked re-run.  On a clean run both paths execute the
    identical cached plan.

    ``boundary`` selects the per-axis global edge mode (DESIGN.md §15):
    ``None``/``"periodic"`` is the historical wrap bit for bit; a string
    applies to every axis, a tuple names each axis, e.g.
    ``boundary=("reflect", "periodic")``."""
    kw = dict(
        hw=hw, backend=None if backend == "auto" else backend,
        tile_m=tile_m, tile_n=tile_n, h_block=h_block,
        z_slab=z_slab, z_block=z_block, w_tile=w_tile, w_block=w_block,
        interpret=interpret, compute_dtype=compute_dtype,
        use_sparse_unit=use_sparse_unit, boundary=boundary,
    )
    if guard:
        from .guard import guarded_stencil_plan
        plan = guarded_stencil_plan(weights, x.shape, x.dtype, t,
                                    watchdog=watchdog, **kw)
    else:
        plan = stencil_plan(weights, x.shape, x.dtype, t, **kw)
    return plan(x)


def explain(
    weights, t: int, dtype_bytes: int = 4,
    hw: pm.HardwareSpec = pm.TPU_V5E_BF16, tile_n: int = 128,
    strip_m: int = 128, h_block: Optional[int] = None,
    z_slab: Optional[int] = None, z_block: Optional[int] = None,
    w_tile: Optional[int] = None, w_block: Optional[int] = None,
    grid_shape=None, tile_m: Optional[int] = None,
    use_sparse_unit: bool = False,
    boundary=None,
) -> Decision:
    """Expose the dispatch decision (scenario, predicted speedup, reason).

    Delegates to ``repro.kernels.plan.decide`` -- the same single decision
    path plan building and the ``auto`` backend consult.  The reason
    string includes the substrate's read-amplification factor and the
    resolved (z_slab, strip_m, h_block, w_tile) geometry for every rank,
    and with ``grid_shape`` the launch's grid steps per call.
    Plans price the geometry they resolve FOR THEIR GRID, so pass
    ``grid_shape`` -- plus the same ``tile_m``/``h_block``/``z_slab``/
    ``z_block``/``w_tile``/``w_block`` pins you would hand
    ``stencil_plan`` -- and the identical resolution runs here,
    guaranteeing ``explain`` agrees with what such a plan actually
    executes (``strip_m`` is then superseded by the resolution).  Without
    ``grid_shape`` the decision is priced at the documented defaults
    (strip_m=128, z_slab=strip_m for 3D, auto blocks, full width), which
    only coincide with plans whose grids resolve to them."""
    spec = spec_from_weights(weights)
    if grid_shape is not None:
        from .common import resolve_substrate_geom
        grid_shape = tuple(int(n) for n in grid_shape)
        geom = resolve_substrate_geom(
            grid_shape, t * spec.radius, dtype_bytes,
            tile_m, h_block, z_slab, z_block, w_tile, w_block)
        strip_m, h_block = geom.strip_m, geom.h_block
        z_slab = geom.z_slab if geom.dim == 3 else None
        z_block = geom.z_block if geom.dim == 3 else None
        w_tile = geom.w_tile if geom.dim >= 2 else None
        w_block = geom.w_block if geom.dim >= 2 else None
    if boundary is not None:
        from repro.stencil.boundary import resolve_boundary
        boundary = resolve_boundary(boundary, spec.dim)
    return decide(spec, t, dtype_bytes, hw,
                  tile_n=tile_n, strip_m=strip_m, h_block=h_block,
                  z_slab=z_slab, z_block=z_block,
                  w_tile=w_tile, w_block=w_block,
                  use_sparse_unit=use_sparse_unit, boundary=boundary,
                  weights=weights, grid_shape=grid_shape)
