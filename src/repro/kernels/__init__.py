"""Pallas TPU kernels for the stencil hot paths (VPU direct, MXU banded),
on the one block-ring halo substrate (kernels.common).

The public surface is the plan API: ``stencil_plan`` compiles the paper's
decision procedure + kernel lowering into a reusable ``StencilPlan``;
``stencil_apply`` is the one-shot compatibility wrapper over it; backends
register through ``repro.kernels.registry``.  ``guarded_stencil_plan``
wraps a plan in the guarded execution layer (failure taxonomy +
degradation ladder, DESIGN.md §11)."""
from .ops import stencil_apply, explain
from .plan import (StencilPlan, stencil_plan, spec_from_weights,
                   plan_cache_stats, plan_cache_max, clear_plan_cache)
from .registry import (register_backend, unregister_backend,
                       registered_backends, get_backend, fallback_ladder)
from .guard import (GuardedExecutionError, GuardedPlan, HaloExchangeError,
                    KernelCompileError, NumericalFaultError, PlanBuildError,
                    VmemOverflowError, classify_failure,
                    guarded_stencil_plan)
from .stencil_direct import stencil_direct
from .stencil_matmul import (stencil_matmul, build_bands, build_bands_nd,
                             band_sparsity)
from .common import (SubstrateGeom, choose_col_blocks, choose_hblock,
                     choose_slab_blocks, choose_strip, choose_strip_blocks,
                     choose_tile, pricing_geom, resolve_strip_blocks,
                     resolve_substrate_geom, substrate_read_amp,
                     vmem_budget_bytes)


def __getattr__(name):
    # Delegates to ops.__getattr__: BACKENDS is computed on access so
    # late-registered plug-in backends show up.
    if name == "BACKENDS":
        from . import ops
        return ops.BACKENDS
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
