"""Guarded plan execution: failure taxonomy + deterministic degradation
ladder (DESIGN.md §11).

The paper's thesis is that MXU stencil execution only wins inside a
sweet spot; outside it -- awkward geometries, deep fusion, VMEM-tight
tiles -- the aggressive regimes are exactly where compiles fail and
numerics drift.  A serving deployment (ROADMAP north star) cannot crash
on the first Mosaic error.  This module makes every plan build and step
*survivable*:

Taxonomy
  Raw XLA / Mosaic / Pallas exceptions are classified by cause into
  :class:`PlanBuildError`, :class:`KernelCompileError`,
  :class:`VmemOverflowError`, :class:`NumericalFaultError`, or
  :class:`HaloExchangeError`, all subclasses of
  :class:`GuardedExecutionError` carrying ``.cause``.

Degradation ladder
  On failure, a :class:`GuardedPlan` retries deterministically:

    requested backend, normal geometry
      -> same backend, DEGRADED geometry (auto pins dropped, VMEM
         budget halved, so ``resolve_substrate_geom`` shrinks
         strip_m / z_slab / w_tile)
      -> registry backends by ``fallback_rank``
         (fused_matmul_reuse -> fused_sparse_matmul -> sparse_matmul
          -> fused_matmul -> matmul -> fused_direct -> direct
          -> reference oracle)

  Each rung failure is classified, recorded in the
  :mod:`repro.core.events` ring buffer, and noted in the plan module's
  negative-result registry (``note_plan_failure``) -- the LRU never
  retains a failed signature, and a repeat request short-circuits
  straight past known-bad rungs (``failed_plan``).  The ladder is a pure
  function of the plan signature and process env, so every shard of a
  distributed mesh lands on the same rung without communicating.

Watchdog
  Opt-in (``watchdog=True`` or ``REPRO_NAN_WATCHDOG=1``): each guarded
  step's output is checked for NaN/Inf on the host; a fault re-runs the
  offending step through the checked reference backend, records a
  :class:`NumericalFaultError` event, and demotes the rung for future
  calls.  Fused bf16 steps are the intended clients.

A clean run records nothing, skips nothing, and returns the *identical*
cached plan object an unguarded ``stencil_plan`` call would -- the guard
layer is invisible until something fails (the ISSUE 6 acceptance bar).
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import events as _events
from repro.core.envutil import env_flag
from repro.testing import faults as _faults
from . import plan as _plan
from . import registry


# ---------------------------------------------------------------------------
# Failure taxonomy
# ---------------------------------------------------------------------------
class GuardedExecutionError(RuntimeError):
    """Base of the guard taxonomy; ``cause`` is the machine-readable tag
    recorded in events and negative-cache entries."""

    cause = "unknown"

    def __init__(self, message: str, *, backend: Optional[str] = None,
                 stage: Optional[str] = None):
        super().__init__(message)
        self.backend = backend
        self.stage = stage


class PlanBuildError(GuardedExecutionError):
    """Host-side plan construction failed (sizing, validation, weight
    composition) before any kernel was traced."""

    cause = "plan_build"


class KernelCompileError(GuardedExecutionError):
    """The kernel failed to trace/lower/compile (Mosaic, XLA, Pallas)."""

    cause = "compile"


class VmemOverflowError(GuardedExecutionError):
    """The compiled working set exceeded VMEM (RESOURCE_EXHAUSTED and
    friends): the tile estimate lied; degrade the geometry."""

    cause = "vmem"


class NumericalFaultError(GuardedExecutionError):
    """A step produced NaN/Inf (watchdog) -- numerics drifted, typically
    deep fusion in bf16."""

    cause = "numerical"


class HaloExchangeError(GuardedExecutionError):
    """The distributed halo exchange (ppermute ring) failed."""

    cause = "halo"


#: Message fragments -> taxonomy, checked in order (most specific first).
#: These deliberately match both real XLA/Mosaic spellings and the
#: injected fakes of repro.testing.faults, so tests exercise the exact
#: classification path production errors take.
_VMEM_MARKERS = ("resource_exhausted", "vmem", "out of memory",
                 "scratch", "memory space")
_COMPILE_MARKERS = ("mosaic", "failed to compile", "lowering",
                    "unsupported", "internal:", "xla", "pallas",
                    "unimplemented", "mlir")
_HALO_MARKERS = ("halo exchange", "ppermute", "collective")
_NUMERIC_MARKERS = ("nan", "non-finite", "not finite", "inf produced")


def classify_failure(exc: BaseException,
                     stage: str = "execute",
                     backend: Optional[str] = None) -> GuardedExecutionError:
    """Wrap a raw exception in its taxonomy class (never raises).

    ``stage`` breaks ties when the message matches nothing: ``"build"``
    failures become :class:`PlanBuildError`, anything at trace/execute
    time defaults to :class:`KernelCompileError` (the conservative guess:
    retrying a different regime is always legal).
    """
    if isinstance(exc, GuardedExecutionError):
        return exc
    msg = str(exc)
    low = msg.lower()
    if any(m in low for m in _HALO_MARKERS):
        cls = HaloExchangeError
    elif any(m in low for m in _VMEM_MARKERS):
        cls = VmemOverflowError
    elif any(m in low for m in _NUMERIC_MARKERS):
        cls = NumericalFaultError
    elif any(m in low for m in _COMPILE_MARKERS):
        cls = KernelCompileError
    elif stage == "build":
        cls = PlanBuildError
    else:
        cls = KernelCompileError
    err = cls(f"[{cls.cause}] {msg}", backend=backend, stage=stage)
    err.__cause__ = exc
    return err


# ---------------------------------------------------------------------------
# Ladder construction
# ---------------------------------------------------------------------------
class _Rung:
    """One ladder position: a backend override + geometry mode."""

    __slots__ = ("backend", "degraded")

    def __init__(self, backend: Optional[str], degraded: bool):
        self.backend = backend      # None = auto (selector decides)
        self.degraded = degraded

    def label(self, resolved: Optional[str] = None) -> str:
        name = self.backend or (f"auto:{resolved}" if resolved else "auto")
        return f"{name}+degraded" if self.degraded else name

    def __repr__(self):
        return f"_Rung({self.label()!r})"


class _EnvPin:
    """Temporarily pin REPRO_VMEM_BUDGET (the degraded-geometry rung):
    auto sizing re-resolves under the shrunken budget and the halved
    value lands in the plan key, so degraded plans never alias normal
    ones.  Restores the prior value even on failure."""

    def __init__(self, budget: Optional[int]):
        self._budget = budget
        self._prior = None
        self._had = False

    def __enter__(self):
        if self._budget is not None:
            self._had = "REPRO_VMEM_BUDGET" in os.environ
            self._prior = os.environ.get("REPRO_VMEM_BUDGET")
            os.environ["REPRO_VMEM_BUDGET"] = str(self._budget)
        return self

    def __exit__(self, *exc):
        if self._budget is not None:
            if self._had:
                os.environ["REPRO_VMEM_BUDGET"] = self._prior
            else:
                os.environ.pop("REPRO_VMEM_BUDGET", None)
        return False


def _start_backend(weights, grid_shape, dtype, t, hw, backend,
                   tile_m, h_block, z_slab, z_block, w_tile, w_block,
                   use_sparse_unit=False):
    """The name the first rung executes: the override if given, else the
    selector's pick -- computed exactly as ``stencil_plan`` itself would,
    so the ladder agrees with the unguarded decision.  Returns ``None``
    when even pricing fails (then the fallback walk uses the full
    ladder)."""
    if backend is not None:
        return backend
    try:
        from .common import resolve_substrate_geom
        spec = _plan.spec_from_weights(weights)
        geom = resolve_substrate_geom(
            tuple(grid_shape), t * spec.radius, np.dtype(dtype).itemsize,
            tile_m, h_block, z_slab, z_block, w_tile, w_block)
        decision = _plan.decide(
            spec, t, dtype_bytes=np.dtype(dtype).itemsize, hw=hw,
            strip_m=geom.strip_m, h_block=geom.h_block,
            z_slab=geom.z_slab if geom.dim == 3 else None,
            z_block=geom.z_block if geom.dim == 3 else None,
            w_tile=geom.w_tile if geom.dim >= 2 else None,
            w_block=geom.w_block if geom.dim >= 2 else None,
            use_sparse_unit=use_sparse_unit)
        return decision.backend
    except Exception:
        return None


# ---------------------------------------------------------------------------
# GuardedPlan
# ---------------------------------------------------------------------------
class GuardedPlan:
    """A StencilPlan wrapper that survives failures by walking the
    degradation ladder.  Mirrors the plan API (``__call__``/``step``/
    ``run``/``explain``) and exposes:

      * ``plan``     -- the live underlying :class:`StencilPlan`;
      * ``backend``  -- the backend actually executing right now;
      * ``degraded`` -- True once any ladder move happened;
      * ``history``  -- ``[{"rung", "cause", "error"}]`` of failed rungs.
    """

    def __init__(self, plan_args: tuple, plan_kwargs: dict,
                 watchdog: Optional[bool] = None):
        self._args = plan_args          # (spec_or_weights, grid, dtype, t)
        self._kwargs = dict(plan_kwargs)
        if watchdog is None:
            watchdog = env_flag("REPRO_NAN_WATCHDOG", False)
        self.watchdog = bool(watchdog)
        self.history: List[dict] = []

        weights = plan_args[0]
        from repro.stencil.spec import StencilSpec
        if isinstance(weights, StencilSpec):
            from repro.stencil.weights import jacobi_weights
            weights = jacobi_weights(weights)
        self._start = _start_backend(
            np.asarray(weights), plan_args[1], plan_args[2], plan_args[3],
            self._kwargs.get("hw", _plan.pm.TPU_V5E_BF16),
            self._kwargs.get("backend"),
            self._kwargs.get("tile_m"), self._kwargs.get("h_block"),
            self._kwargs.get("z_slab"), self._kwargs.get("z_block"),
            self._kwargs.get("w_tile"), self._kwargs.get("w_block"),
            self._kwargs.get("use_sparse_unit", False))

        requested = self._kwargs.get("backend")  # None = auto
        self._rungs: List[_Rung] = [_Rung(requested, False),
                                    _Rung(requested, True)]
        for name in registry.fallback_ladder(after=self._start):
            self._rungs.append(_Rung(name, False))
        if not any(r.backend == "reference" for r in self._rungs):
            self._rungs.append(_Rung("reference", False))  # terminal rung
        self._idx = 0
        self._plan = None
        self._checked = None            # lazily built reference re-run plan
        self._build_current()

    # -- rung plumbing --------------------------------------------------
    def _rung_call_kwargs(self, rung: _Rung) -> dict:
        kw = dict(self._kwargs)
        kw["backend"] = rung.backend
        if rung.degraded:
            # Degraded geometry: drop explicit pins so the shared N-D rule
            # (resolve_substrate_geom) re-sizes everything under the
            # halved budget pinned by _EnvPin.  ``boundary`` is NOT in this
            # list: it is semantics, not geometry -- every rung (and the
            # checked reference re-run) must honor the plan's boundary
            # modes or the ladder would silently change the answer.
            for g in ("tile_m", "tile_n", "h_block", "z_slab", "z_block",
                      "w_tile", "w_block"):
                kw[g] = None
        kw.pop("hw", None)
        return kw

    def _rung_env(self, rung: _Rung) -> _EnvPin:
        if not rung.degraded:
            return _EnvPin(None)
        from .common import vmem_budget_bytes
        return _EnvPin(max(vmem_budget_bytes() // 2, 1))

    def _rung_key(self, rung: _Rung):
        kw = self._rung_call_kwargs(rung)
        kw.pop("use_cache", None)
        return _plan.plan_signature(
            *self._args, hw=self._kwargs.get("hw", _plan.pm.TPU_V5E_BF16),
            **kw)[0]

    def _note_failure(self, rung: _Rung, err: GuardedExecutionError,
                      stage: str) -> None:
        with self._rung_env(rung):
            key = self._rung_key(rung)
        _plan.note_plan_failure(key, err.cause, rung.label(self._start),
                                stage=stage)
        self.history.append({"rung": rung.label(self._start),
                             "cause": err.cause,
                             "error": str(err)[:200]})
        _events.record("guard_failure", cause=err.cause,
                       rung=rung.label(self._start), stage=stage,
                       error=str(err)[:200])

    def _advance(self, rung: _Rung) -> None:
        self._idx += 1
        if self._idx >= len(self._rungs):
            raise GuardedExecutionError(
                "degradation ladder exhausted (no rung survived); see "
                "plan_cache_stats() and repro.core.events for the record")
        _plan.record_fallback()
        _events.record("guard_fallback", frm=rung.label(self._start),
                       to=self._rungs[self._idx].label(self._start))

    def _build_current(self) -> None:
        """Build the plan for the current rung, advancing past rungs whose
        build fails or whose signature is already known-bad."""
        while True:
            rung = self._rungs[self._idx]
            with self._rung_env(rung):
                key = self._rung_key(rung)
                neg = _plan.failed_plan(key)
                if neg is not None:
                    _events.record("guard_skip", rung=rung.label(self._start),
                                   cause=neg["cause"])
                    self._idx += 1
                    if self._idx >= len(self._rungs):
                        raise GuardedExecutionError(
                            "degradation ladder exhausted: every rung is "
                            "negative-cached; clear_plan_cache() to retry")
                    continue
                try:
                    self._plan = _plan.stencil_plan(
                        *self._args,
                        hw=self._kwargs.get("hw", _plan.pm.TPU_V5E_BF16),
                        **self._rung_call_kwargs(rung))
                    return
                except Exception as exc:  # noqa: BLE001 -- classified below
                    err = classify_failure(exc, stage="build",
                                           backend=rung.label(self._start))
                    self._note_failure(rung, err, stage="build")
                    self._advance(rung)

    # -- introspection --------------------------------------------------
    @property
    def plan(self):
        return self._plan

    @property
    def backend(self) -> str:
        return self._plan.backend

    @property
    def degraded(self) -> bool:
        return self._idx > 0

    @property
    def rung(self) -> str:
        return self._rungs[self._idx].label(self._start)

    @property
    def grid_shape(self):
        return self._plan.grid_shape

    @property
    def batch(self):
        return self._plan.batch

    @property
    def input_shape(self):
        return self._plan.input_shape

    @property
    def decision(self):
        return self._plan.decision

    def explain(self) -> str:
        lines = [self._plan.explain()]
        if self.degraded:
            lines.append(f"  guard    : DEGRADED to rung {self.rung!r} "
                         f"after {len(self.history)} failure(s)")
            for h in self.history:
                lines.append(f"    - {h['rung']}: {h['cause']} "
                             f"({h['error'][:80]})")
        else:
            lines.append("  guard    : clean (no degradation)")
        return "\n".join(lines)

    def __repr__(self):
        return (f"GuardedPlan(rung={self.rung!r}, degraded={self.degraded}, "
                f"failures={len(self.history)})")

    # -- execution ------------------------------------------------------
    def _checked_rerun(self, x):
        """Re-run one step through the checked reference backend (the
        watchdog's recovery path -- never passes through fault hooks)."""
        if self._checked is None:
            kw = dict(self._kwargs)
            kw.pop("hw", None)
            kw.pop("use_cache", None)
            for g in ("tile_m", "tile_n", "h_block", "z_slab", "z_block",
                      "w_tile", "w_block"):
                kw.pop(g, None)
            kw["backend"] = "reference"
            self._checked = _plan.stencil_plan(
                *self._args, hw=self._kwargs.get("hw", _plan.pm.TPU_V5E_BF16),
                **kw)
        return self._checked(x)

    def __call__(self, x: jax.Array) -> jax.Array:
        if tuple(x.shape) != self._plan.input_shape:
            # caller bug, not a kernel failure: propagate raw
            return self._plan(x)
        tracing = isinstance(x, jax.core.Tracer)
        while True:
            rung = self._rungs[self._idx]
            try:
                y = self._plan(x)
                if not tracing:
                    y = _faults.corrupt_output(y)
                    jax.block_until_ready(y)
            except Exception as exc:  # noqa: BLE001 -- classified below
                err = classify_failure(exc, stage="execute",
                                       backend=rung.label(self._start))
                self._note_failure(rung, err, stage="execute")
                self._advance(rung)
                self._build_current()
                continue
            if self.watchdog and not tracing:
                if not bool(jnp.isfinite(y).all()):
                    err = NumericalFaultError(
                        f"[numerical] NaN/Inf in step output "
                        f"(backend {self.backend!r})",
                        backend=rung.label(self._start), stage="execute")
                    self._note_failure(rung, err, stage="execute")
                    _events.record("guard_watchdog",
                                   rung=rung.label(self._start),
                                   action="checked_rerun")
                    y = self._checked_rerun(x)
                    # demote for FUTURE calls; this step already recovered
                    self._advance(rung)
                    self._build_current()
            return y

    def step(self, x: jax.Array) -> jax.Array:
        return self(x)

    def run(self, x: jax.Array, n_steps: int) -> jax.Array:
        if n_steps < 0:
            raise ValueError(f"n_steps must be >= 0, got {n_steps}")
        for _ in range(n_steps):
            x = self(x)
        return x


def guarded_stencil_plan(spec_or_weights, grid_shape, dtype, t: int = 1,
                         *, watchdog: Optional[bool] = None,
                         **kwargs) -> GuardedPlan:
    """Build a :class:`GuardedPlan`: ``stencil_plan`` arguments plus
    ``watchdog`` (None = the ``REPRO_NAN_WATCHDOG`` env flag).

    Raw argument errors (bad ``t``, rank mismatch, unknown backend) raise
    immediately and unguarded -- the ladder only absorbs *kernel*
    failures, never caller bugs.

    ``batch=B`` plans are guarded per-batch (DESIGN.md §12): a failing
    rung demotes the WHOLE bucket -- every request in it -- and the
    degraded rung re-executes the full batched input, so no request is
    ever answered from a half-failed launch.  The ``batch``/``batch_mode``
    kwargs ride through every rung unchanged (only geometry pins are
    dropped on the degraded rung)."""
    # the raw-argument gate: validates before any rung is attempted
    _plan.plan_signature(spec_or_weights, grid_shape, dtype, t,
                         **{k: v for k, v in kwargs.items()
                            if k != "use_cache"})
    return GuardedPlan((spec_or_weights, tuple(int(n) for n in grid_shape),
                        dtype, t), kwargs, watchdog=watchdog)
