"""StencilPlan: compile-once execution plans for the stencil runtime.

The paper's decision procedure (§4.1 criteria) is analytic -- it depends
only on (spec, t, dtype, hardware), never on the grid values -- so a
serving deployment (ROADMAP north star: millions of steps over a fixed
grid/spec) should run it ONCE.  ``stencil_plan`` does exactly that:

  * spec inference from dense weights (or an explicit ``StencilSpec``),
  * backend selection (``repro.core.selector.select_backend``, enumerating
    the backend registry's priced candidates),
  * strip/tile sizing and weight preprocessing (fused-kernel composition,
    tiling validation) inside the chosen backend's ``build`` hook,
  * halo-exchange planning when a device ``mesh`` is given,

then returns a :class:`StencilPlan` whose ``plan(x)`` / ``plan.step(x)`` /
``plan.run(x, n)`` execute with zero re-analysis -- the executable is a
single jitted callable, so repeated calls hit XLA's compile cache and
never re-enter selection, sizing, or weight composition.

Plans are cached process-wide, keyed on the full execution signature
(weights digest, grid shape, dtype, t, hardware, tiling, batch axis,
interpret, compute dtype, sharding, backend override) with hit/miss
counters (:func:`plan_cache_stats`).  ``repro.kernels.ops.stencil_apply``
survives as a thin wrapper that builds-or-fetches a plan per call.

``stencil_plan(..., batch=B)`` folds a leading batch axis through the
kernels (DESIGN.md §12): one plan invocation advances ``B`` independent
grids of the SAME geometry, bitwise-equal to a loop of ``B`` unbatched
invocations.  The serving engine (``repro.serve``) is the intended
client -- it coalesces queued requests by plan signature and dispatches
one batched launch per bucket.  Cache mutation is lock-protected: the
engine builds and fetches plans from worker threads.
"""
from __future__ import annotations

import dataclasses
import hashlib
import threading
import time
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro.core import perfmodel as pm
from repro.core import trace
from repro.core.selector import Decision, select_backend
from repro.stencil.boundary import (BoundaryLike, boundary_label,
                                    is_periodic, resolve_boundary)
from repro.stencil.spec import StencilSpec
from repro.stencil.weights import jacobi_weights
from . import registry


def _default_interpret() -> bool:
    return jax.default_backend() != "tpu"


def spec_from_weights(weights) -> StencilSpec:
    """Infer (shape, d, r) from a dense kernel's support."""
    w = np.asarray(weights)
    radius = (w.shape[0] - 1) // 2
    dim = w.ndim
    box_points = np.count_nonzero(w)
    star_points = 2 * dim * radius + 1
    shape = "star" if box_points <= star_points else "box"
    return StencilSpec(shape, dim, radius)


def decide(
    spec: StencilSpec, t: int, dtype_bytes: int,
    hw: pm.HardwareSpec = pm.TPU_V5E_BF16,
    tile_n: int = 128, strip_m: int = 128,
    h_block: Optional[int] = None,
    z_slab: Optional[int] = None,
    z_block: Optional[int] = None,
    w_tile: Optional[int] = None,
    w_block: Optional[int] = None,
    use_sparse_unit: bool = False,
    boundary: BoundaryLike = None,
    weights=None,
    grid_shape=None,
) -> Decision:
    """THE decision path: plan building, ``stencil_apply(backend="auto")``
    and ``ops.explain`` all consult this one function, so they can never
    disagree about the priced ``Decision``.  ``z_slab``/``z_block`` matter
    only for 3D specs (the halo-plane substrate's depth geometry);
    ``w_tile``/``w_block`` price the column-tiled W substrate
    (DESIGN.md §10; ``None``/0 = full width); ``use_sparse_unit`` admits
    the sparse-compacted backends as priced candidates (DESIGN.md §14);
    ``boundary`` (DESIGN.md §15) is recorded in the decision's reason --
    the in-kernel fills are FLOP-free, so it never moves the pricing.
    ``grid_shape`` (the grid the geometry was resolved for) puts the
    launch's ``grid_steps`` into the reason's substrate clause.
    A VPU tap-sum pick also records the column-shift form its kernel
    traces (``stencil_direct.column_shift_form`` of ``weights``, else of
    the spec's own Jacobi weights): ``| column shifts=roll`` or
    ``slice``."""
    decision = select_backend(spec, t, dtype_bytes=dtype_bytes, hw=hw,
                              tile_n=tile_n, strip_m=strip_m,
                              h_block=h_block, z_slab=z_slab,
                              z_block=z_block, w_tile=w_tile,
                              w_block=w_block,
                              use_sparse_unit=use_sparse_unit,
                              boundary=boundary, grid_shape=grid_shape)
    if decision.backend not in ("direct", "fused_direct"):
        return decision
    from .stencil_direct import column_shift_form
    mode_x = resolve_boundary(boundary, spec.dim)[-1]
    form = column_shift_form(
        jacobi_weights(spec) if weights is None else weights, mode_x,
        wrap_x=not w_tile)
    return dataclasses.replace(
        decision, reason=f"{decision.reason} | column shifts={form}")


class StencilPlan:
    """A compiled, reusable stencil execution plan.

    Built by :func:`stencil_plan`; calling the plan advances the grid ``t``
    time steps.  Attributes of interest:

      * ``decision``  -- the priced :class:`Decision` (what "auto" picks and
        why), always populated, even under a backend override;
      * ``backend``   -- the backend the plan actually executes;
      * ``halo_plan`` -- dict describing the halo-exchange schedule
        (distributed plans only, else ``None``);
      * ``build_time_s`` -- host seconds spent building (selection, sizing,
        weight composition; excludes XLA compilation, which happens on the
        first call);
      * ``compiles`` / ``compile_s`` -- executables made inside this plan's
        calls (XLA compiles and persistent-cache loads) and their seconds,
        counted by a ``jax.monitoring`` listener (:func:`plan_cache_stats`
        sums them over all plans);
      * ``fn``        -- the underlying jitted callable.

    Each call runs inside a ``repro.plan.call`` profiler span
    (``repro.core.trace``).
    """

    def __init__(self, *, spec, weights, grid_shape, dtype, t, hw, backend,
                 decision, fn, tile_m, tile_n, interpret, compute_dtype,
                 mesh=None, shard_spec=None, dist_mode=None, halo_plan=None,
                 key=None, build_time_s=0.0, batch=None, batch_mode=None,
                 ctx=None, boundary=None):
        self.spec = spec
        self.weights = weights
        self.grid_shape = grid_shape
        #: Resolved per-axis boundary modes (DESIGN.md §15); ``None`` =
        #: all periodic (the historical plans).
        self.boundary = boundary
        self.batch = batch
        self.batch_mode = batch_mode
        self.dtype = dtype
        self.t = t
        self.hw = hw
        self.backend = backend
        self.decision = decision
        self.fn = fn
        self.tile_m = tile_m
        self.tile_n = tile_n
        self.interpret = interpret
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.shard_spec = shard_spec
        self.dist_mode = dist_mode
        self.halo_plan = halo_plan
        self.key = key
        self.build_time_s = build_time_s
        #: The registry PlanContext the plan was built from (None for
        #: plans reconstructed without one); lets the auditor re-derive
        #: the declared launch structure of an existing plan.
        self.ctx = ctx
        #: repro.audit.AuditReport attached at build time when auditing
        #: is enabled (``stencil_plan(..., audit=True)`` / REPRO_AUDIT=1);
        #: None otherwise.  Cached plans keep the report of their build.
        self.audit_report = None
        self.compiles = 0
        self.compile_s = 0.0

    # -- execution ------------------------------------------------------
    @property
    def input_shape(self) -> Tuple[int, ...]:
        """The array shape one invocation consumes: ``grid_shape`` for an
        unbatched plan, ``(batch,) + grid_shape`` for a batched one."""
        if self.batch is None:
            return self.grid_shape
        return (self.batch,) + self.grid_shape

    def __call__(self, x: jax.Array) -> jax.Array:
        if tuple(x.shape) != self.input_shape:
            raise ValueError(
                f"plan was built for input {self.input_shape} "
                f"(grid {self.grid_shape}, batch {self.batch}), got "
                f"{x.shape}; build a new plan for a new geometry")
        with jax.profiler.TraceAnnotation(trace.PLAN_CALL):
            outer = getattr(_CALLING, "plan", None)
            _CALLING.plan = self
            try:
                return self.fn(x)
            finally:
                _CALLING.plan = outer

    def step(self, x: jax.Array) -> jax.Array:
        """Alias for ``plan(x)``: one invocation = ``t`` time steps."""
        return self(x)

    def run(self, x: jax.Array, n_steps: int) -> jax.Array:
        """``n_steps`` plan invocations (``n_steps * t`` time steps)."""
        if n_steps < 0:
            raise ValueError(f"n_steps must be >= 0, got {n_steps}")
        for _ in range(n_steps):
            x = self(x)
        return x

    # -- introspection --------------------------------------------------
    def explain(self) -> str:
        """Human-readable account of what the plan does and why."""
        d = self.decision
        lines = [
            f"StencilPlan {self.spec.name} t={self.t} grid={self.grid_shape} "
            + ("" if self.batch is None
               else f"batch={self.batch} ({self.batch_mode}) ")
            + f"dtype={np.dtype(self.dtype).name} on {self.hw.name}",
            f"  executes : {self.backend}"
            + ("" if self.backend == d.backend
               else f" (override; auto would pick {d.backend})"),
            f"  scenario : {d.scenario}",
            f"  speedup  : {d.predicted_speedup:.2f}x (best matrix vs vector)",
            f"  reason   : {d.reason}",
            "  candidates (effective FLOP/s): "
            + ", ".join(f"{k}={v:.3g}" for k, v in d.candidates.items()),
        ]
        if self.boundary is not None and not is_periodic(self.boundary):
            lines.insert(2, f"  boundary : {boundary_label(self.boundary)}")
        if self.halo_plan is not None:
            hp = self.halo_plan
            line = (f"  halo plan: mode={hp['mode']} depth={hp['halo_depth']} "
                    f"exchanges/call={hp['exchanges_per_call']} "
                    f"bytes/shard/call={hp['halo_bytes_per_call']}")
            if "interior_fraction" in hp:
                line += (" overlap: interior_fraction="
                         f"{hp['interior_fraction']:.3f}")
            lines.append(line)
        return "\n".join(lines)

    def __repr__(self) -> str:
        return (f"StencilPlan({self.spec.name}, t={self.t}, "
                f"grid={self.grid_shape}, backend={self.backend!r}, "
                + ("" if self.batch is None else f"batch={self.batch}, ")
                + f"distributed={self.mesh is not None})")


# ---------------------------------------------------------------------------
# Plan cache: bounded LRU (plans pin weights, jitted executables, and --
# for distributed plans -- the mesh, so a long-running server sweeping
# geometries must not grow without bound).
#
# One re-entrant lock serializes every cache/counter mutation: the serving
# engine (repro.serve.engine) builds and fetches plans from dispatcher
# threads, and the guard ladder mutates the negative registry from
# whichever thread hit the failure.  Plan BUILDING stays outside the lock
# (it traces and jits -- seconds, not microseconds); two threads racing to
# build the same signature both build, the second insert wins, and the
# counters stay consistent (hits + misses == lookups).
# ---------------------------------------------------------------------------
import os
from collections import OrderedDict

_LOCK = threading.RLock()

#: Default maximum cached plans; least-recently-used entries are evicted
#: beyond the bound.  Override per process with the REPRO_PLAN_CACHE_SIZE
#: environment variable (read at every eviction, so tests and long-running
#: servers can retune without reimporting).
PLAN_CACHE_MAX = 512

_CACHE: "OrderedDict" = OrderedDict()
_STATS = {"hits": 0, "misses": 0,
          # guard-layer counters (repro.kernels.guard): plan builds that
          # raised, plan executions that raised, degradation-ladder moves,
          # and negative-cache short-circuits.  All zero unless something
          # actually failed -- asserted by the clean-run acceptance tests.
          "build_failures": 0, "exec_failures": 0,
          "fallbacks": 0, "negative_hits": 0,
          # static-auditor counters (repro.audit): audited plan builds
          # and total check violations found there.  Violations never
          # block the build -- they count, attach, and surface through
          # plan_cache_stats so CI and the serving loop can gate on them.
          "audits_run": 0, "audit_violations": 0,
          # executables made inside plan calls (compiles and persistent-
          # cache loads) and their seconds; per plan on StencilPlan.
          "compiles": 0, "compile_s": 0.0}

#: The ``jax.monitoring`` event JAX records around making an executable:
#: an XLA compile or a load from the persistent compilation cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: The plan whose call is running on this thread (innermost first: a
#: sharded plan's per-shard plans are called while it traces).
_CALLING = threading.local()


def _on_event_duration(event: str, secs: float, **_kw) -> None:
    """Attribute an executable made during a plan's call to that plan.
    JAX compiles synchronously on the calling thread, so the plan on
    this thread is the one whose call caused it."""
    if event != COMPILE_EVENT:
        return
    plan = getattr(_CALLING, "plan", None)
    if plan is None:
        return
    with _LOCK:
        plan.compiles += 1
        plan.compile_s += secs
        _STATS["compiles"] += 1
        _STATS["compile_s"] += secs


jax.monitoring.register_event_duration_secs_listener(_on_event_duration)

#: Negative-result registry: signature key -> {"cause", "backend", "stamp"}.
#: A signature lands here when its build/execution failed, so the guard
#: ladder short-circuits repeat failures straight past the known-bad rung
#: without re-attempting the (possibly slow) doomed compile.  Entries
#: expire after ``plan_cache_max()`` cache churn -- a transient failure
#: (e.g. memory pressure) must not blacklist a signature forever.
_NEGATIVE: "OrderedDict" = OrderedDict()
_churn = 0  # total successful + negative insertions, the expiry clock


def plan_cache_max() -> int:
    """The effective LRU bound: ``REPRO_PLAN_CACHE_SIZE`` if set (must be a
    positive integer), else :data:`PLAN_CACHE_MAX`."""
    from repro.core.envutil import env_int
    return env_int("REPRO_PLAN_CACHE_SIZE", PLAN_CACHE_MAX, minimum=1)


def plan_cache_stats() -> dict:
    """Cache + guard counters: hits/misses/size plus ``build_failures``,
    ``exec_failures``, ``fallbacks``, ``negative_hits``, ``negative_size``,
    and ``compiles``/``compile_s`` made inside plan calls.  The snapshot
    is atomic -- taken under the cache lock."""
    with _LOCK:
        out = dict(_STATS)
        out["size"] = len(_CACHE)
        out["negative_size"] = len(_NEGATIVE)
    return out


def clear_plan_cache() -> None:
    global _churn
    with _LOCK:
        _CACHE.clear()
        _NEGATIVE.clear()
        _churn = 0
        for k in _STATS:
            _STATS[k] = 0


def _tick_churn() -> None:
    """Advance the expiry clock and drop negative entries older than one
    full cache turnover (``plan_cache_max()`` insertions).  Callers must
    hold ``_LOCK``."""
    global _churn
    _churn += 1
    bound = plan_cache_max()
    while _NEGATIVE:
        stamp = next(iter(_NEGATIVE.values()))["stamp"]
        if _churn - stamp <= bound:
            break
        _NEGATIVE.popitem(last=False)


def note_plan_failure(key, cause: str, backend: str,
                      stage: str = "build") -> None:
    """Record a failed signature in the negative registry (guard layer).

    The failed plan itself is evicted from the LRU -- a failed build or a
    plan whose execution raised must never be served again."""
    with _LOCK:
        _CACHE.pop(key, None)
        _STATS["build_failures" if stage == "build" else "exec_failures"] += 1
        _NEGATIVE[key] = {"cause": cause, "backend": backend, "stamp": _churn}
        _NEGATIVE.move_to_end(key)
        _tick_churn()


def failed_plan(key):
    """The negative entry for ``key`` if present and unexpired, else None.
    A hit counts toward ``negative_hits`` -- it means the guard skipped a
    known-doomed rung."""
    with _LOCK:
        entry = _NEGATIVE.get(key)
        if entry is None:
            return None
        if _churn - entry["stamp"] > plan_cache_max():
            del _NEGATIVE[key]
            return None
        _STATS["negative_hits"] += 1
        return dict(entry)


def discard_plan(key) -> bool:
    """Evict ``key`` from the plan LRU (no-op if absent)."""
    with _LOCK:
        return _CACHE.pop(key, None) is not None


def record_fallback() -> None:
    """One degradation-ladder move (guard layer bookkeeping)."""
    with _LOCK:
        _STATS["fallbacks"] += 1


#: dtype -> canonical name memo.  ``np.dtype(dt).name`` walks numpy's
#: dtype-printing machinery (~5us); on the serving submit path that is
#: paid per REQUEST, so the handful of dtypes a process ever sees are
#: cached.  Keys are the raw ``dt`` arguments (dtype objects, scalar
#: types, strings -- all hashable and all stable aliases of their name).
_DTYPE_NAMES: Dict = {}


def _dtype_name(dt) -> str:
    name = _DTYPE_NAMES.get(dt)
    if name is None:
        name = _DTYPE_NAMES[dt] = np.dtype(dt).name
    return name


def _weights_key(w: np.ndarray) -> Tuple:
    digest = hashlib.sha1(np.ascontiguousarray(w).tobytes()).hexdigest()
    return (w.shape, _dtype_name(w.dtype), digest)


def _dtype_key(dt) -> str:
    return _dtype_name(dt)


# ---------------------------------------------------------------------------
# Plan construction
# ---------------------------------------------------------------------------
#: How a batched plan folds its leading batch axis (DESIGN.md §12):
#:   "vmap" -- jax.vmap over the single-grid runner (Pallas prepends a
#:            batch grid dimension: one launch covers the batch);
#:   "map"  -- jax.lax.map (a scanned loop of the single-grid runner
#:            inside ONE jitted computation: per-request VMEM working set
#:            identical to the unbatched plan, dispatch paid once);
#:   "auto" -- "map" under interpret mode (the scan amortizes Python
#:            dispatch, which dominates emulated kernels), "vmap" when
#:            compiling for real hardware (the batched grid dimension is
#:            free there).
#: Both are bitwise-equal to a loop of unbatched plans -- the equivalence
#: sweep in tests/test_serve_batch.py asserts it per backend/dtype/rank.
BATCH_MODES = ("auto", "vmap", "map")


def _resolve_batch_mode(batch_mode: str, interpret: bool) -> str:
    if batch_mode not in BATCH_MODES:
        raise ValueError(f"batch_mode must be one of {BATCH_MODES}, "
                         f"got {batch_mode!r}")
    if batch_mode == "auto":
        return "map" if interpret else "vmap"
    return batch_mode


def plan_signature(
    spec_or_weights: Union[StencilSpec, np.ndarray],
    grid_shape: Sequence[int],
    dtype,
    t: int = 1,
    *,
    hw: pm.HardwareSpec = pm.TPU_V5E_BF16,
    mesh=None,
    shard_spec: Optional[Sequence[Optional[str]]] = None,
    dist_mode: str = "fused",
    backend: Optional[str] = None,
    tile_m: Optional[int] = None,
    tile_n: Optional[int] = None,
    h_block: Optional[int] = None,
    z_slab: Optional[int] = None,
    z_block: Optional[int] = None,
    w_tile: Optional[int] = None,
    w_block: Optional[int] = None,
    batch: Optional[int] = None,
    batch_mode: str = "auto",
    interpret: Optional[bool] = None,
    compute_dtype=None,
    use_sparse_unit: bool = False,
    boundary: BoundaryLike = None,
) -> Tuple:
    """Validate plan arguments and return ``(key, weights, grid_shape,
    interpret)`` -- the deterministic cache signature WITHOUT building.

    This is the raw-argument gate: genuine user errors (bad ``t``, rank
    mismatch, unknown backend) raise here, unguarded, so the guard layer
    never mistakes a caller bug for a kernel failure.  The key is pure --
    it depends only on the arguments plus the process env (VMEM budget,
    registry generation), never on device state -- which is what lets
    every shard of a distributed mesh agree on the same fallback rung
    without communicating.
    """
    if t < 1:
        raise ValueError(f"fusion depth must be >= 1, got {t}")
    if batch is not None:
        if int(batch) < 1:
            raise ValueError(f"batch must be >= 1, got {batch}")
        batch = int(batch)
        if mesh is not None:
            raise ValueError(
                "batched plans do not compose with distributed meshes yet; "
                "shard the request stream across hosts instead "
                "(repro.serve coalesces per host)")
    if backend is not None:
        registry.get_backend(backend)          # fail fast on unknown names
    if mesh is not None and shard_spec is None:
        raise ValueError("a mesh-parameterized plan needs shard_spec "
                         "(one mesh-axis name per grid dim, None=unsharded)")

    if isinstance(spec_or_weights, StencilSpec):
        weights = jacobi_weights(spec_or_weights)
    else:
        weights = np.asarray(spec_or_weights)
    grid_shape = tuple(int(n) for n in grid_shape)
    if len(grid_shape) != weights.ndim:
        raise ValueError(
            f"grid rank {len(grid_shape)} != kernel rank {weights.ndim}; "
            "the plan's grid_shape must match the stencil dimensionality")
    # Resolved per-axis modes land in the key: a reflect×periodic plan
    # must never alias the periodic plan of the same geometry.  Unknown
    # modes / length mismatches raise here, in the caller's frame.
    boundary_key = resolve_boundary(boundary, len(grid_shape))
    if interpret is None:
        interpret = _default_interpret()
    # The RESOLVED fold mode lands in the key (pure: a function of the
    # arguments + resolved interpret), so "auto" on CPU and an explicit
    # "map" share one plan while "vmap" plans never alias them.
    batch_key = None if batch is None \
        else (batch, _resolve_batch_mode(batch_mode, interpret))

    shard_key = None
    if mesh is not None:
        shard_key = (id(mesh), tuple(shard_spec), dist_mode)
    # registry.generation() invalidates plans whose selection (or builder,
    # under overwrite=True) predates a registry change -- a newly priced
    # backend must win future auto plans, not be masked by the cache.
    # The effective VMEM budget is part of the key: auto geometry depends
    # on it, so retuning REPRO_VMEM_BUDGET must never serve stale plans.
    # So is the in-kernel scope switch: a scoped plan compiles other
    # kernels than an unscoped one.
    from .common import vmem_budget_bytes
    key = (_weights_key(weights), grid_shape, _dtype_key(dtype), t, hw,
           shard_key, backend, tile_m, tile_n, h_block, z_slab, z_block,
           w_tile, w_block, batch_key, vmem_budget_bytes(), interpret,
           None if compute_dtype is None else _dtype_key(compute_dtype),
           bool(use_sparse_unit), boundary_key, registry.generation(),
           trace.kernel_scopes())
    return key, weights, grid_shape, interpret


def stencil_plan(
    spec_or_weights: Union[StencilSpec, np.ndarray],
    grid_shape: Sequence[int],
    dtype,
    t: int = 1,
    *,
    hw: pm.HardwareSpec = pm.TPU_V5E_BF16,
    mesh=None,
    shard_spec: Optional[Sequence[Optional[str]]] = None,
    dist_mode: str = "fused",
    backend: Optional[str] = None,
    tile_m: Optional[int] = None,
    tile_n: Optional[int] = None,
    h_block: Optional[int] = None,
    z_slab: Optional[int] = None,
    z_block: Optional[int] = None,
    w_tile: Optional[int] = None,
    w_block: Optional[int] = None,
    batch: Optional[int] = None,
    batch_mode: str = "auto",
    interpret: Optional[bool] = None,
    compute_dtype=None,
    use_sparse_unit: bool = False,
    use_cache: bool = True,
    audit: Optional[bool] = None,
    boundary: BoundaryLike = None,
) -> StencilPlan:
    """Build (or fetch from cache) a compiled stencil execution plan.

    Args:
      spec_or_weights: a dense ``(2r+1)^d`` kernel, or a ``StencilSpec``
        (then the deterministic Jacobi weights of that spec are used).
      grid_shape: global grid shape the plan is specialized to; its rank
        must match the kernel's (1D, 2D and 3D grids are supported --
        DESIGN.md §9).
      dtype: grid dtype.
      t: fusion depth -- time steps advanced per plan invocation.
      hw: hardware model consulted by the selector.
      mesh / shard_spec: when given, the plan drives the distributed
        halo-exchange stepper; ``shard_spec`` names one mesh axis per grid
        dim (``None`` entries = unsharded dims).  ``dist_mode`` is
        ``"fused"`` (one depth-``t*r`` exchange per invocation),
        ``"stepwise"`` (``t`` depth-``r`` exchanges) or ``"overlap"``
        (stepwise's schedule with the interior update overlapping each
        in-flight exchange; needs exactly one sharded dim).
      backend: override the selector's choice with any registered backend
        name (``repro.kernels.registry.registered_backends()``).
      tile_m/tile_n: explicit strip height / column-tile width (``None`` =
        auto-sized exactly as the kernels themselves would).
      h_block: halo sub-block height of the strip substrate (``None`` =
        auto; ``strip_m`` reads whole neighbor strips); part of the cache
        key.
      z_slab/z_block: 3D grids only -- slab depth and halo-plane block of
        the halo-plane substrate (``None`` = auto); part of the cache key.
      w_tile/w_block: column-tiled W substrate (DESIGN.md §10; ``None`` =
        auto -- full width whenever it fits the VMEM budget, ``0`` pins
        full width); part of the cache key, as is the effective VMEM
        budget (``REPRO_VMEM_BUDGET``) the auto sizing consulted.
      batch: when given, the plan consumes ``(batch,) + grid_shape`` and
        advances ``batch`` independent grids per invocation, bitwise-equal
        to a loop of unbatched plans (DESIGN.md §12).  Geometry sizing and
        selection stay per-grid -- the batch axis never widens the VMEM
        working set of a "map" plan.  Part of the cache key.
      batch_mode: how the batch axis folds -- see :data:`BATCH_MODES`
        ("auto" = "map" under interpret, "vmap" compiled).
      interpret: Pallas interpret mode; ``None`` = off-TPU default.
      use_sparse_unit: admit the sparse-compacted backends
        (``sparse_matmul``/``fused_sparse_matmul``, DESIGN.md §14) as
        priced auto candidates; part of the cache key.
      boundary: per-axis boundary modes (DESIGN.md §15) -- one of
        ``periodic | zero | reflect | replicate`` per grid axis (a bare
        string applies to every axis; ``None`` entries and ``None``
        itself mean periodic, the historical behavior bit for bit), e.g.
        ``boundary=("reflect", "periodic")``.  Part of the cache key.
      use_cache: bypass the process-wide plan cache when ``False``.
      audit: run the static auditor (repro.audit) over the built plan and
        attach its report as ``plan.audit_report`` (``None`` defers to the
        ``REPRO_AUDIT`` env flag).  Violations never fail the build: they
        bump the ``audit_violations`` counter in :func:`plan_cache_stats`
        and surface in the attached report.  Not part of the cache key --
        a cached plan keeps the report of the build that audited it.
    """
    key, weights, grid_shape, interpret = plan_signature(
        spec_or_weights, grid_shape, dtype, t, hw=hw, mesh=mesh,
        shard_spec=shard_spec, dist_mode=dist_mode, backend=backend,
        tile_m=tile_m, tile_n=tile_n, h_block=h_block, z_slab=z_slab,
        z_block=z_block, w_tile=w_tile, w_block=w_block,
        batch=batch, batch_mode=batch_mode,
        interpret=interpret, compute_dtype=compute_dtype,
        use_sparse_unit=use_sparse_unit, boundary=boundary)
    modes = resolve_boundary(boundary, len(grid_shape))
    with _LOCK:
        if use_cache and key in _CACHE:
            _STATS["hits"] += 1
            _CACHE.move_to_end(key)
            return _CACHE[key]
        _STATS["misses"] += 1

    with jax.profiler.TraceAnnotation(trace.PLAN_BUILD):
        t0 = time.perf_counter()
        spec = spec_from_weights(weights)
        # Selection prices the geometry the kernels will actually resolve for
        # this grid (fused-regime halo t*r), so the read-amplification term in
        # the decision matches the substrate that runs; tile_n keeps its
        # historical 128 pricing default when unpinned.
        from .common import resolve_substrate_geom
        geom_px = resolve_substrate_geom(
            grid_shape, t * spec.radius, np.dtype(dtype).itemsize,
            tile_m, h_block, z_slab, z_block, w_tile, w_block)
        decision = decide(
            spec, t, dtype_bytes=np.dtype(dtype).itemsize, hw=hw,
            tile_n=tile_n if tile_n is not None else 128,
            strip_m=geom_px.strip_m, h_block=geom_px.h_block,
            z_slab=geom_px.z_slab if geom_px.dim == 3 else None,
            z_block=geom_px.z_block if geom_px.dim == 3 else None,
            w_tile=geom_px.w_tile if geom_px.dim >= 2 else None,
            w_block=geom_px.w_block if geom_px.dim >= 2 else None,
            use_sparse_unit=use_sparse_unit,
            boundary=modes, weights=weights, grid_shape=grid_shape,
        )
        exec_backend = backend if backend is not None else decision.backend

        ctx = registry.PlanContext(
            spec=spec, weights=weights, grid_shape=grid_shape,
            dtype=np.dtype(dtype), t=t, tile_m=tile_m, tile_n=tile_n,
            interpret=interpret, compute_dtype=compute_dtype, h_block=h_block,
            z_slab=z_slab, z_block=z_block, w_tile=w_tile, w_block=w_block,
            boundary=modes, name=exec_backend,
            scopes=trace.kernel_scopes(),
        )

        halo_plan = None
        resolved_mode = None
        if mesh is None:
            run = registry.get_backend(exec_backend).build(ctx)
            if batch is not None:
                from .common import fold_batch
                resolved_mode = _resolve_batch_mode(batch_mode, interpret)
                run = fold_batch(run, resolved_mode)
            fn = jax.jit(run, compiler_options=trace.compiler_options(
                ctx.scopes, interpret))
        else:
            fn, halo_plan = _build_distributed(
                mesh, tuple(shard_spec), dist_mode, ctx, exec_backend)

        plan = StencilPlan(
            spec=spec, weights=weights, grid_shape=grid_shape,
            dtype=np.dtype(dtype), t=t, hw=hw, backend=exec_backend,
            decision=decision, fn=fn, tile_m=tile_m, tile_n=tile_n,
            interpret=interpret, compute_dtype=compute_dtype, mesh=mesh,
            shard_spec=None if shard_spec is None else tuple(shard_spec),
            dist_mode=dist_mode if mesh is not None else None,
            halo_plan=halo_plan, key=key,
            build_time_s=time.perf_counter() - t0,
            batch=None if batch is None else int(batch),
            batch_mode=resolved_mode,
            ctx=ctx, boundary=modes,
        )
    from repro.core.envutil import env_flag
    if audit if audit is not None else env_flag("REPRO_AUDIT"):
        _attach_audit(plan, ctx, exec_backend, decision, geom_px,
                      t * spec.radius)
    if use_cache:
        with _LOCK:
            # Read (and validate) the bound BEFORE inserting: a malformed
            # REPRO_PLAN_CACHE_SIZE must never leave the cache growing with
            # eviction disabled.
            bound = plan_cache_max()
            _CACHE[key] = plan
            while len(_CACHE) > bound:
                _CACHE.popitem(last=False)
            _tick_churn()
    return plan


def _attach_audit(plan, ctx, exec_backend, decision, geom_px,
                  priced_halo) -> None:
    """Run the static auditor over the freshly built plan and attach the
    report (repro.audit, DESIGN.md §13).  Never raises: violations count
    into the plan stats and live in ``plan.audit_report``; an auditor
    crash records itself as a violation rather than failing the build.
    Distributed and batched plans wrap the launch in collectives /
    batch folds the block-level auditor does not model, so they attach
    an exempt report instead of false violations.
    """
    from repro import audit as _audit

    try:
        if plan.mesh is not None or plan.batch is not None:
            report = _audit.AuditReport(
                backend=exec_backend, grid_shape=tuple(ctx.grid_shape),
                t=ctx.t, dtype=str(np.dtype(ctx.dtype)),
                exempt=("distributed stepper wraps the launch in halo "
                        "collectives" if plan.mesh is not None
                        else "batch fold wraps the launch"))
        else:
            report = _audit.audit_context(ctx, exec_backend)
            report.checks.append(_audit.audit_reason_read_amp(
                decision.reason, tuple(ctx.grid_shape), geom_px,
                priced_halo, np.dtype(ctx.dtype).itemsize))
    except Exception as e:  # pragma: no cover - auditor must not break builds
        report = _audit.AuditReport(
            backend=exec_backend, grid_shape=tuple(ctx.grid_shape),
            t=ctx.t, dtype=str(np.dtype(ctx.dtype)),
            checks=[_audit.AuditCheck("audit/crashed", False,
                                      actual=repr(e))])
    plan.audit_report = report
    with _LOCK:
        _STATS["audits_run"] += 1
        _STATS["audit_violations"] += len(report.violations)


def _build_distributed(mesh, axis_names, dist_mode, ctx, exec_backend):
    """Wire the halo-exchange stepper around the chosen local backend."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (halo_bytes_per_step,
                                           make_distributed_stepper,
                                           pallas_local_apply)

    if len(axis_names) != len(ctx.grid_shape):
        raise ValueError(f"shard_spec {axis_names} must name one mesh axis "
                         f"per grid dim of {ctx.grid_shape}")
    local_shape = []
    for n, ax in zip(ctx.grid_shape, axis_names):
        parts = mesh.shape[ax] if ax is not None else 1
        if n % parts:
            raise ValueError(f"grid dim {n} not divisible by mesh axis "
                             f"{ax!r} ({parts} shards)")
        local_shape.append(n // parts)
    local_shape = tuple(local_shape)

    # reference executes through the stepper's built-in jnp local update;
    # every other registered backend plugs in as a Pallas local apply.
    local = None if exec_backend == "reference" else pallas_local_apply(
        exec_backend, interpret=ctx.interpret,
        tile_m=ctx.tile_m, tile_n=ctx.tile_n, h_block=ctx.h_block,
        z_slab=ctx.z_slab, z_block=ctx.z_block,
        w_tile=ctx.w_tile, w_block=ctx.w_block)
    # The LOCAL plan stays periodic whatever ctx.boundary says: the global
    # boundary is realized in the halo extension (mode pads + edge-shard
    # masks), and the kernel's modulo wrap only pollutes the discarded
    # halo ring (DESIGN.md §15).
    stepper = make_distributed_stepper(
        mesh, axis_names, ctx.weights, t=ctx.t, mode=dist_mode,
        local_apply=local, boundary=ctx.boundary)
    sharding = NamedSharding(mesh, P(*axis_names))
    fn = jax.jit(stepper, in_shardings=sharding, out_shardings=sharding,
                 compiler_options=trace.compiler_options(ctx.scopes,
                                                         ctx.interpret))

    r = ctx.radius
    halo_plan = {
        "mode": dist_mode,
        "halo_depth": r * ctx.t if dist_mode == "fused" else r,
        "exchanges_per_call": 1 if dist_mode == "fused" else ctx.t,
        "halo_bytes_per_call": halo_bytes_per_step(
            local_shape, axis_names, r, ctx.t, dist_mode,
            np.dtype(ctx.dtype).itemsize),
        "local_shape": local_shape,
    }
    if dist_mode == "overlap":
        # Fraction of the local block whose update is computed while the
        # exchange is in flight -- the latency-hiding headroom explain()
        # surfaces.
        frac = 1.0
        for m, ax in zip(local_shape, axis_names):
            if ax is not None:
                frac *= max(m - 2 * r, 0) / m
        halo_plan["interior_fraction"] = frac
    return fn, halo_plan
