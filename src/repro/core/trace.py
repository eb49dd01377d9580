"""The program's trace spans: their names and the in-kernel scope switch.

Every span the program emits goes into the JAX profiler trace, which
holds host annotations and device ops on one clock.  Names start with
``repro.``:

* ``repro.plan.call`` -- host span around each ``StencilPlan`` call
  (dispatch, and the compile on a plan's first call);
* ``repro.plan.build`` -- host span over the window that
  ``StencilPlan.build_time_s`` times;
* ``repro.dist.exchange`` / ``repro.dist.local`` -- ``jax.named_scope``
  around the sharded stepper's halo exchange and its local kernel
  apply; they land in the ``op_name`` metadata of every device op they
  cover and cost nothing at run time;
* ``repro.substrate.assemble`` / ``repro.kernel.compute`` -- scopes
  inside each Pallas kernel body, which Mosaic lowers to
  ``tpu.trace_start``/``tpu.trace_stop`` (level 10).  Unlike the others
  they are compiled in only while :func:`kernel_scopes` is on: off
  leaves every kernel byte-identical to an unscoped build.

The switch is read when a plan is built and is part of the plan cache
key, so a scoped plan never aliases an unscoped one.  A compiled scoped
plan also asks XLA for :data:`SCOPED_COMPILER_OPTIONS`: on a TPU v5e
the scopes reach the profiler (the device plane's ``XLA TraceMe`` line)
only from an executable compiled with them; neither the bare scopes nor
the profiler's ``tpu_trace_mode`` options show them.
"""
from __future__ import annotations

import contextlib

PLAN_CALL = "repro.plan.call"
PLAN_BUILD = "repro.plan.build"
DIST_EXCHANGE = "repro.dist.exchange"
DIST_LOCAL = "repro.dist.local"
SUBSTRATE_ASSEMBLE = "repro.substrate.assemble"
KERNEL_COMPUTE = "repro.kernel.compute"

#: Compile options of a scoped plan's executable on the chip.
SCOPED_COMPILER_OPTIONS = {"xla_enable_custom_call_region_trace": True}

_kernel_scopes = False


def kernel_scopes() -> bool:
    """Whether plans built now compile the in-kernel scopes in."""
    return _kernel_scopes


@contextlib.contextmanager
def kernel_scopes_on(on: bool = True):
    """Build plans with the in-kernel scopes ``on`` inside the block."""
    global _kernel_scopes
    before, _kernel_scopes = _kernel_scopes, bool(on)
    try:
        yield
    finally:
        _kernel_scopes = before


def scope(name: str, on: bool = True):
    """``jax.named_scope(name)`` when ``on``, else a no-op context."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope(name)


def compiler_options(scopes: bool, interpret: bool):
    """The ``jax.jit`` compile options of a plan: the scoped plan's on
    the chip, none otherwise (interpret mode runs on XLA:CPU, which has
    no such option)."""
    return dict(SCOPED_COMPILER_OPTIONS) if scopes and not interpret \
        else None
