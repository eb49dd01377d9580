"""Backend registry: every execution regime registers through one interface.

A *backend* is one way to advance the grid ``t`` time steps -- the five
regimes of the strip substrate (VPU direct/fused, MXU sequential /
monolithic / intermediate-reuse), their two sparse-compacted MXU
variants, and the pure-jnp reference oracle all register here via
:func:`register_backend` and are addressed by name from ``stencil_plan`` /
``stencil_apply``.

Each :class:`BackendDef` carries two callables:

  * ``build(ctx)`` -- consume a :class:`PlanContext` (stencil spec, dense
    weights, grid geometry, tiling, dtype) and return the executable
    ``run(x) -> y`` for ``t`` steps.  All host-side analysis (tile sizing,
    weight composition, validation) happens HERE, once per plan; ``run`` is
    jitted by the plan, so nothing in it re-executes per call.
  * ``price(pctx)`` -- optional analytic throughput (effective stencil
    FLOP/s) under a :class:`repro.core.selector.PricingContext`, or ``None``
    when the backend is not a candidate for that workload (e.g. the reuse
    regime degenerates at t=1).  ``select_backend`` enumerates priced
    backends instead of a hard-coded dict, so new regimes (e.g. a sparse
    unit) become selectable just by registering.

Every Pallas regime runs on the one block-ring substrate (kernels.common,
DESIGN.md §3); a caller who wants a coarser read pattern pins the ring
(``h_block=strip_m`` reads whole neighbor strips) instead of picking
another backend.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import numpy as np

from repro.core import perfmodel as pm
from repro.stencil.boundary import is_periodic, resolve_boundary
from repro.stencil.spec import StencilSpec
from repro.stencil.weights import fuse_weights
from .common import (SubstrateGeom, check_tpu_tiling, choose_tile,
                     launch_geometry, resolve_substrate_geom,
                     validate_tiling)
from . import ref as _ref
from .stencil_direct import stencil_direct
from .stencil_matmul import build_bands_nd, stencil_matmul
from .stencil_sparse import compact_bands, stencil_sparse_matmul


# ---------------------------------------------------------------------------
# Plan-build context handed to backend builders
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class PlanContext:
    """Everything a backend builder may consume, resolved once per plan."""

    spec: StencilSpec
    weights: np.ndarray          # dense (2r+1)^d base kernel, host-side
    grid_shape: Tuple[int, ...]
    dtype: np.dtype
    t: int
    tile_m: Optional[int]        # user-requested; None = auto per kernel rule
    tile_n: Optional[int]
    interpret: bool
    compute_dtype: object = None
    h_block: Optional[int] = None   # None = auto
    z_slab: Optional[int] = None    # 3D grids: slab depth (None = auto)
    z_block: Optional[int] = None   # 3D grids: halo-plane block (None = auto)
    w_tile: Optional[int] = None    # None = auto, 0 = full width (fast path)
    w_block: Optional[int] = None   # column halo block (None = auto)
    #: Per-axis boundary spec (DESIGN.md §15), resolved by the plan layer
    #: to one mode per grid axis; ``None`` = all periodic (historical).
    boundary: Optional[Tuple[str, ...]] = None
    #: The name the plan's kernels carry in the compiled program and the
    #: profiler trace (the backend's); ``None`` leaves Pallas's default.
    name: Optional[str] = None
    #: Compile the in-kernel trace scopes in (``repro.core.trace``).
    scopes: bool = False

    @property
    def radius(self) -> int:
        return (self.weights.shape[0] - 1) // 2

    def fused_weights(self) -> np.ndarray:
        """Radius-``t*r`` composed kernel (monolithic fusion operand)."""
        return fuse_weights(self.weights, self.t)

    def resolve_geom(self, halo: int) -> SubstrateGeom:
        """Full substrate geometry under the kernels' own N-D rule.

        ``halo`` is the vertical/leading halo of the regime being built;
        the carried x-halo of a column-tiled launch equals it for the
        square kernels this repo builds, so it doubles as ``x_halo``.
        """
        return resolve_substrate_geom(self.grid_shape, halo,
                                      np.dtype(self.dtype).itemsize,
                                      self.tile_m, self.h_block,
                                      self.z_slab, self.z_block,
                                      self.w_tile, self.w_block, halo)

    def resolve_tile_n(self) -> int:
        """Column-chunk width of the banded contraction (MXU paths)."""
        wid = self.grid_shape[-1]
        return choose_tile(wid) if self.tile_n is None else min(self.tile_n, wid)

    def kernel_kwargs(self, geom: SubstrateGeom) -> dict:
        """The substrate-geometry and trace kwargs the strip kernels
        accept."""
        kw = dict(tile_m=geom.strip_m, h_block=geom.h_block,
                  boundary=self.boundary, name=self.name,
                  scopes=self.scopes)
        if geom.dim >= 2:
            kw.update(w_tile=geom.w_tile, w_block=geom.w_block)
        if geom.dim == 3:
            kw.update(z_slab=geom.z_slab, z_block=geom.z_block)
        return kw

    def validate(self, geom: SubstrateGeom, tile_n: int, halo: int,
                 radius: int) -> None:
        validate_tiling(self.grid_shape, geom.strip_m, tile_n, halo, radius,
                        geom.h_block,
                        geom.z_slab if geom.dim == 3 else None, geom.z_block,
                        geom.w_tile, geom.w_block, halo,
                        boundary=self.boundary)
        if not self.interpret:
            check_tpu_tiling(self.grid_shape, geom,
                             np.dtype(self.dtype).itemsize)


# ---------------------------------------------------------------------------
# Audit hooks: what a backend declares it will launch (repro.audit)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LaunchAudit:
    """One declared kernel launch of a backend, in auditable terms.

    The static auditor (``repro.audit``) turns this into the
    :class:`~repro.kernels.common.LaunchGeometry` the substrate builds for
    it and proves the analytic model against that structure -- the hook
    resolves geometry through the SAME ``PlanContext`` methods the builder
    uses, so the declaration cannot drift from the built plan.
    """

    geom: SubstrateGeom
    grid_shape: Tuple[int, ...]   # TRUE user grid (pre-lift)
    halo: int                     # leading/vertical halo of this launch
    x_halo: int                   # carried x-halo (column-tiled only)
    t_inner: int                  # in-VMEM steps inside the launch
    weights: np.ndarray           # kernel-rank operand (1D grids lifted)
    radius: int                   # per-step x radius of ``weights``
    engine: str                   # "direct" | "matmul" | "sparse_matmul"
    tile_n: int = 0               # MXU column-chunk width
    bands_shape: Optional[Tuple[int, ...]] = None
    n_offsets: int = 0            # banded operand rows actually built
    #: Sparse-compacted launches (engine "sparse_matmul") also declare the
    #: per-band gather metadata: ``band_lo[p]`` the first kept contraction
    #: row (the input-gather offset) and ``band_spans[p]`` the tap span
    #: (kept rows = tile_n + span).  ``bands_shape`` is then the PACKED
    #: operand's shape, whose row count proves the kept-row fraction S.
    band_lo: Optional[Tuple[int, ...]] = None
    band_spans: Optional[Tuple[int, ...]] = None
    #: Per-axis boundary modes at the TRUE grid rank (``None`` = periodic);
    #: ``launch_geometry`` lifts 1D grids exactly as the kernels do.
    boundary: Optional[Tuple[str, ...]] = None

    def launch_geometry(self):
        """The exact structure the substrate launches for this geometry."""
        return launch_geometry(self.grid_shape, self.geom,
                               self.halo, self.x_halo,
                               boundary=self.boundary)


@dataclasses.dataclass(frozen=True)
class AuditSpec:
    """A backend's full audit declaration: its launches, in run order."""

    launches: Tuple[LaunchAudit, ...] = ()
    #: Non-None opts the backend out with a recorded reason (the
    #: reference oracle has no launch structure to audit).
    exempt: Optional[str] = None


def _launch_audit(ctx: PlanContext, geom: SubstrateGeom, w_op, t_inner: int,
                  engine: str) -> LaunchAudit:
    """Describe one launch exactly as the kernels resolve it: 1D grids
    lift the operand to (1, N) with zero vertical halo; column-tiled
    launches carry ``t_inner * radius`` of x support."""
    w_op = np.asarray(w_op)
    if len(ctx.grid_shape) == 1:
        w_op = w_op[None, :]
    radius = (w_op.shape[-1] - 1) // 2
    halo = t_inner * ((w_op.shape[0] - 1) // 2)
    x_halo = t_inner * radius if geom.w_tile else 0
    extra = {}
    if engine == "matmul":
        tile_n = ctx.resolve_tile_n()
        offsets, bands = build_bands_nd(w_op.astype(np.float32), tile_n)
        extra = dict(tile_n=tile_n, bands_shape=tuple(bands.shape),
                     n_offsets=len(offsets))
    elif engine == "sparse_matmul":
        tile_n = ctx.resolve_tile_n()
        offsets, bands = build_bands_nd(w_op.astype(np.float32), tile_n)
        row_index, packed = compact_bands(offsets, bands)
        extra = dict(tile_n=tile_n, bands_shape=tuple(packed.shape),
                     n_offsets=len(offsets),
                     band_lo=tuple(int(ix[0]) for ix in row_index),
                     band_spans=tuple(int(ix.size) - tile_n
                                      for ix in row_index))
    return LaunchAudit(geom=geom, grid_shape=tuple(ctx.grid_shape),
                       halo=halo, x_halo=x_halo, t_inner=t_inner,
                       weights=w_op, radius=radius, engine=engine,
                       boundary=resolve_boundary(ctx.boundary,
                                                 len(ctx.grid_shape)),
                       **extra)


def _audit_direct(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.radius), ctx.weights,
                      1, "direct")
    return AuditSpec(launches=(l,) * ctx.t)


def _audit_fused_direct(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.t * ctx.radius), ctx.weights,
                      ctx.t, "direct")
    return AuditSpec(launches=(l,))


def _audit_matmul(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.radius), ctx.weights,
                      1, "matmul")
    return AuditSpec(launches=(l,) * ctx.t)


def _audit_fused_matmul(ctx: PlanContext) -> AuditSpec:
    wf = ctx.fused_weights()
    R = (wf.shape[0] - 1) // 2
    l = _launch_audit(ctx, ctx.resolve_geom(R), wf, 1, "matmul")
    return AuditSpec(launches=(l,))


def _audit_fused_matmul_reuse(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.t * ctx.radius), ctx.weights,
                      ctx.t, "matmul")
    return AuditSpec(launches=(l,))


def _audit_sparse_matmul(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.radius), ctx.weights,
                      1, "sparse_matmul")
    return AuditSpec(launches=(l,) * ctx.t)


def _audit_fused_sparse_matmul(ctx: PlanContext) -> AuditSpec:
    l = _launch_audit(ctx, ctx.resolve_geom(ctx.t * ctx.radius), ctx.weights,
                      ctx.t, "sparse_matmul")
    return AuditSpec(launches=(l,))


def _audit_exempt(reason: str) -> Callable:
    def audit(ctx: PlanContext) -> AuditSpec:
        return AuditSpec(exempt=reason)
    return audit


@dataclasses.dataclass(frozen=True)
class BackendDef:
    name: str
    build: Callable[[PlanContext], Callable]
    price: Optional[Callable] = None   # price(PricingContext) -> float | None
    description: str = ""
    unit: Optional[str] = None         # "vector" | "matrix" | None (other)
    #: Position on the guard layer's degradation ladder (DESIGN.md §11):
    #: lower = more aggressive, higher = more conservative.  ``None`` means
    #: the backend is never a fallback target (plug-ins that ask for no
    #: rank).  The reference oracle carries the largest rank so the ladder
    #: always terminates on it.
    fallback_rank: Optional[int] = None
    #: ``audit(ctx) -> AuditSpec`` declares the backend's launches for the
    #: static auditor (repro.audit); ``None`` means "not yet auditable"
    #: (plug-ins), reported as exempt rather than violating.
    audit: Optional[Callable] = None


_REGISTRY: Dict[str, BackendDef] = {}
#: Bumped on every (un)registration; folded into plan-cache keys so plans
#: built against an older registry never mask a newly registered candidate.
_generation = 0


def generation() -> int:
    return _generation


def register_backend(name: str, build: Callable, price: Callable = None,
                     description: str = "", unit: str = None,
                     overwrite: bool = False,
                     fallback_rank: Optional[int] = None,
                     audit: Callable = None) -> BackendDef:
    """Register an execution backend under ``name``.

    ``build(ctx: PlanContext) -> run(x)`` constructs the executable;
    ``price(pctx) -> Optional[float]`` (optional) makes it an auto-selection
    candidate; ``unit`` classifies it for Decision bookkeeping ("vector" or
    "matrix" -- the predicted matrix-vs-vector speedup considers only
    matrix-unit candidates); ``fallback_rank`` (optional) places it on the
    guard layer's degradation ladder (see :func:`fallback_ladder`);
    ``audit(ctx) -> AuditSpec`` (optional) declares its launches for the
    static auditor (repro.audit).
    Re-registering an existing name raises unless ``overwrite``.
    """
    global _generation
    if name == "auto":
        raise ValueError("'auto' is the selection policy, not a backend")
    if name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {name!r} already registered "
                         "(pass overwrite=True to replace)")
    bd = BackendDef(name=name, build=build, price=price,
                    description=description, unit=unit,
                    fallback_rank=fallback_rank, audit=audit)
    _REGISTRY[name] = bd
    _generation += 1
    return bd


def unregister_backend(name: str) -> None:
    """Remove a registered backend (primarily for tests/plug-in teardown)."""
    global _generation
    if _REGISTRY.pop(name, None) is not None:
        _generation += 1


def get_backend(name: str) -> BackendDef:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown backend {name!r}; registered: "
            f"{tuple(_REGISTRY)} (or 'auto')") from None


def registered_backends() -> Tuple[str, ...]:
    """Names of all registered backends, in registration order."""
    return tuple(_REGISTRY)


def priced_candidates(pctx) -> Dict[str, float]:
    """Evaluate every priced backend under ``pctx``; skip non-candidates."""
    out: Dict[str, float] = {}
    for bd in _REGISTRY.values():
        if bd.price is None:
            continue
        v = bd.price(pctx)
        if v is not None:
            out[bd.name] = v
    return out


def candidate_units() -> Dict[str, Optional[str]]:
    """Registered name -> unit classification ("vector"/"matrix"/None)."""
    return {name: bd.unit for name, bd in _REGISTRY.items()}


def fallback_ladder(after: Optional[str] = None) -> Tuple[str, ...]:
    """Ranked backends in degradation order (most aggressive first).

    ``after=name`` returns only the rungs strictly more conservative than
    ``name`` -- the remaining ladder once ``name`` has failed.  A backend
    with no rank (plug-ins) yields the FULL ladder: an unranked
    regime that fails falls back onto the standard sequence from the top.
    """
    ranked = sorted((bd for bd in _REGISTRY.values()
                     if bd.fallback_rank is not None),
                    key=lambda bd: bd.fallback_rank)
    names = tuple(bd.name for bd in ranked)
    if after is None:
        return names
    cut = _REGISTRY.get(after)
    if cut is None or cut.fallback_rank is None:
        return names
    return tuple(bd.name for bd in ranked
                 if bd.fallback_rank > cut.fallback_rank)


# ---------------------------------------------------------------------------
# Builders: the strip-substrate regimes + reference.
# Each resolves its tiling/operands at build time and closes over them, so
# plan execution re-derives nothing.
# ---------------------------------------------------------------------------
def _build_reference(ctx: PlanContext) -> Callable:
    w, t, b = ctx.weights, ctx.t, ctx.boundary

    def run(x):
        return _ref.stencil_direct_ref(x, w, t, boundary=b)
    return run


def _build_direct(ctx: PlanContext) -> Callable:
    """t sequential VPU kernel launches, halo r per step."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom = ctx.resolve_geom(r)
    ctx.validate(geom, ctx.grid_shape[-1], r, r)
    kw = ctx.kernel_kwargs(geom)
    interp = ctx.interpret

    def run(x):
        for _ in range(t):
            x = stencil_direct(x, w, t=1, interpret=interp, **kw)
        return x
    return run


def _build_fused_direct(ctx: PlanContext) -> Callable:
    """One VPU kernel, t in-VMEM steps (temporal fusion, halo t*r)."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom = ctx.resolve_geom(t * r)
    ctx.validate(geom, ctx.grid_shape[-1], t * r, r)
    kw = ctx.kernel_kwargs(geom)
    interp = ctx.interpret

    def run(x):
        return stencil_direct(x, w, t=t, interpret=interp, **kw)
    return run


def _build_matmul(ctx: PlanContext) -> Callable:
    """t sequential MXU banded contractions, halo r per step."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom, tile_n = ctx.resolve_geom(r), ctx.resolve_tile_n()
    ctx.validate(geom, tile_n, r, r)
    kw = ctx.kernel_kwargs(geom)
    interp, cdt = ctx.interpret, ctx.compute_dtype

    def run(x):
        for _ in range(t):
            x = stencil_matmul(x, w, t=1, tile_n=tile_n, interpret=interp,
                               compute_dtype=cdt, **kw)
        return x
    return run


def _build_fused_matmul(ctx: PlanContext) -> Callable:
    """Monolithic fusion: ONE contraction of the composed radius-t*r kernel."""
    if ctx.t > 1 and not is_periodic(ctx.boundary):
        # One application of the composed kernel sees ONE boundary
        # extension at depth t*r, but every non-periodic mode re-applies
        # per step (DESIGN.md §15) -- the regime cannot represent that.
        raise ValueError(
            "fused_matmul (monolithic fusion) cannot honor non-periodic "
            f"boundaries at t={ctx.t}: the composed radius-t*r kernel "
            "bakes a single boundary extension into all t steps; use "
            "fused_matmul_reuse (per-step fills) or t=1")
    wf = ctx.fused_weights()
    R = (wf.shape[0] - 1) // 2
    geom, tile_n = ctx.resolve_geom(R), ctx.resolve_tile_n()
    ctx.validate(geom, tile_n, R, R)
    kw = ctx.kernel_kwargs(geom)
    interp, cdt = ctx.interpret, ctx.compute_dtype

    def run(x):
        return stencil_matmul(x, wf, t=1, tile_n=tile_n, interpret=interp,
                              compute_dtype=cdt, **kw)
    return run


def _build_fused_matmul_reuse(ctx: PlanContext) -> Callable:
    """Intermediate reuse: t radius-r contractions, VMEM intermediates."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom, tile_n = ctx.resolve_geom(t * r), ctx.resolve_tile_n()
    ctx.validate(geom, tile_n, t * r, r)
    kw = ctx.kernel_kwargs(geom)
    interp, cdt = ctx.interpret, ctx.compute_dtype

    def run(x):
        return stencil_matmul(x, w, t=t, tile_n=tile_n, interpret=interp,
                              compute_dtype=cdt, **kw)
    return run


def _build_sparse_matmul(ctx: PlanContext) -> Callable:
    """t sequential sparse-compacted MXU contractions, halo r per step."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom, tile_n = ctx.resolve_geom(r), ctx.resolve_tile_n()
    ctx.validate(geom, tile_n, r, r)
    kw = ctx.kernel_kwargs(geom)
    interp, cdt = ctx.interpret, ctx.compute_dtype

    def run(x):
        for _ in range(t):
            x = stencil_sparse_matmul(x, w, t=1, tile_n=tile_n,
                                      interpret=interp, compute_dtype=cdt,
                                      **kw)
        return x
    return run


def _build_fused_sparse_matmul(ctx: PlanContext) -> Callable:
    """Intermediate reuse on the compacted operand: t radius-r sparse
    contractions in one kernel, VMEM intermediates."""
    w, t, r = ctx.weights, ctx.t, ctx.radius
    geom, tile_n = ctx.resolve_geom(t * r), ctx.resolve_tile_n()
    ctx.validate(geom, tile_n, t * r, r)
    kw = ctx.kernel_kwargs(geom)
    interp, cdt = ctx.interpret, ctx.compute_dtype

    def run(x):
        return stencil_sparse_matmul(x, w, t=t, tile_n=tile_n,
                                     interpret=interp, compute_dtype=cdt,
                                     **kw)
    return run


# ---------------------------------------------------------------------------
# Pricers: the selector's candidate set, one per selectable regime.  The
# unfused/fused VPU and MXU pairs share a throughput model and partition on
# fusion depth, preserving the historical candidate naming (``direct`` vs
# ``fused_direct`` etc.).  The reference backend is unpriced: it exists
# for debugging and must never win selection.
# ---------------------------------------------------------------------------
def _price_direct(p):
    return p.comparison.vector.actual_flops if p.workload.t == 1 else None


def _price_fused_direct(p):
    return p.comparison.vector.actual_flops if p.workload.t > 1 else None


def _price_matmul(p):
    return p.comparison.matrix.actual_flops if p.workload.t == 1 else None


def _price_fused_matmul(p):
    return p.comparison.matrix.actual_flops if p.workload.t > 1 else None


def _price_fused_matmul_reuse(p):
    # t=1 reuse degenerates to "matmul"; only offered at depth.  z_slab
    # (3D) and w_tile (column-tiled substrate) feed the dim-aware beta;
    # both are None/0 for full-width 1D/2D workloads.
    if p.workload.t == 1:
        return None
    return pm.perf_matrix_reuse(p.workload, p.hw, p.s_reuse,
                                p.strip_m, p.z_slab,
                                p.w_tile or None).actual_flops


def _price_sparse_matmul(p):
    # Candidates only when the user opts into the sparse unit (DESIGN.md
    # §14): compaction's effective-FLOP reduction is real on any MXU, but
    # the selection policy treats it as the Sparse-Tensor-Core regime the
    # paper prices, flipped on explicitly.  Priced from the COMPACTED
    # operand: kept-row fraction * (1 + gather overhead) scales the dense
    # matrix FLOPs.
    if not p.use_sparse_unit or p.workload.t != 1:
        return None
    return pm.perf_sparse_banded(
        p.workload, p.hw, p.s_mono, p.kept_mono,
        pm.compaction_overhead(p.tile_n)).actual_flops


def _price_fused_sparse_matmul(p):
    if not p.use_sparse_unit or p.workload.t == 1:
        return None
    return pm.perf_sparse_banded_reuse(
        p.workload, p.hw, p.s_reuse, p.kept_reuse,
        pm.compaction_overhead(p.tile_n), p.strip_m, p.z_slab,
        p.w_tile or None).actual_flops


# Fallback ranks order the degradation ladder from most aggressive (deep
# fusion, MXU, VMEM-hungry) to most conservative (reference oracle): each
# rung drops one source of fragility -- intermediate reuse, then the MXU,
# then temporal fusion, then Pallas entirely.
register_backend("direct", _build_direct, _price_direct,
                 "t sequential VPU kernel steps (halo r per step)",
                 unit="vector", fallback_rank=50, audit=_audit_direct)
register_backend("fused_direct", _build_fused_direct, _price_fused_direct,
                 "one VPU kernel, t in-VMEM steps (temporal fusion)",
                 unit="vector", fallback_rank=40, audit=_audit_fused_direct)
register_backend("matmul", _build_matmul, _price_matmul,
                 "t sequential MXU banded contractions", unit="matrix",
                 fallback_rank=30, audit=_audit_matmul)
register_backend("fused_matmul", _build_fused_matmul, _price_fused_matmul,
                 "monolithic fusion: one radius-t*r banded contraction",
                 unit="matrix", fallback_rank=20, audit=_audit_fused_matmul)
register_backend("fused_matmul_reuse", _build_fused_matmul_reuse,
                 _price_fused_matmul_reuse,
                 "one MXU kernel, t radius-r contractions, VMEM intermediates",
                 unit="matrix", fallback_rank=10,
                 audit=_audit_fused_matmul_reuse)
# Sparse-compacted pair (DESIGN.md §14): ladder rungs between the reuse
# regime and monolithic fusion -- compaction only drops exact-zero band
# rows, so these rungs are bitwise-safe fallbacks for box kernels too.
register_backend("fused_sparse_matmul", _build_fused_sparse_matmul,
                 _price_fused_sparse_matmul,
                 "one MXU kernel, t sparse-compacted radius-r contractions, "
                 "VMEM intermediates", unit="matrix", fallback_rank=12,
                 audit=_audit_fused_sparse_matmul)
register_backend("sparse_matmul", _build_sparse_matmul, _price_sparse_matmul,
                 "t sequential sparse-compacted MXU contractions",
                 unit="matrix", fallback_rank=16, audit=_audit_sparse_matmul)
register_backend("reference", _build_reference,
                 description="pure-jnp oracle (debug)", fallback_rank=1000,
                 audit=_audit_exempt("pure-jnp oracle: no launch structure "
                                     "to audit"))
