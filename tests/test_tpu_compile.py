"""Compile the main path's kernels for a TPU v5e that is described, not
attached: the chip's own compiler (Mosaic) refuses what interpret mode
accepts -- blocks off the (sublane, 128) tiling, primitives without a
TPU lowering, scoped-VMEM overflow -- so these tests guard every plan
shape the engine runs at real widths without any chip time.  Nothing
executes; a compile that passes is not a chip run.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and the test workers each import this
file.  The persistent compilation cache stays off around the compiles --
an entry written for a described chip cannot be read back here.
"""
import base64
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import trace
from repro.kernels import stencil_plan
from repro.kernels.common import check_tpu_tiling, resolve_substrate_geom
from repro.stencil import StencilSpec, make_weights

STRIP_BACKENDS = ("direct", "fused_direct", "matmul", "fused_matmul",
                  "fused_matmul_reuse", "sparse_matmul",
                  "fused_sparse_matmul")
#: The ring pinned to whole neighbor strips on the 10240^2 grid: three
#: full 32-row strips per output strip (read amp 3).
WHOLE_STRIP = dict(tile_m=32, h_block=32)

GRID_2D = (10240, 10240)
GRID_3D = (512, 512, 512)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    return SingleDeviceSharding(topo.devices[0])


def _depth(backend: str) -> int:
    return 2 if backend.startswith("fused") else 1


def _compile(one_chip, spec, grid, dtype, t, **kw):
    """Build a compiled-mode plan (which checks the tiling rule at build)
    and compile it for one described v5e chip; returns (plan, HLO text)."""
    w = make_weights(spec, seed=0)
    plan = stencil_plan(w, grid, dtype, t, interpret=False, use_cache=False,
                        **kw)
    arg = jax.ShapeDtypeStruct(plan.input_shape, plan.dtype,
                               sharding=one_chip)
    text = plan.fn.lower(arg).compile().as_text()
    assert "tpu_custom_call" in text, "no Pallas kernel in the program"
    return plan, text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("backend", STRIP_BACKENDS)
def test_2d_strip_backends_compile(one_chip, backend, dtype):
    plan, _ = _compile(one_chip, StencilSpec("box", 2, 1), GRID_2D, dtype,
                       _depth(backend), backend=backend)
    assert plan.backend == backend and plan.interpret is False


@pytest.mark.parametrize("backend", STRIP_BACKENDS[:5])
def test_2d_wholestrip_foils_compile(one_chip, backend):
    """The core regimes on the ring pinned to h_block = strip_m."""
    _compile(one_chip, StencilSpec("box", 2, 1), GRID_2D, jnp.float32,
             _depth(backend), backend=backend, **WHOLE_STRIP)


@pytest.mark.parametrize("backend", STRIP_BACKENDS)
def test_3d_strip_backends_compile(one_chip, backend):
    _compile(one_chip, StencilSpec("star", 3, 1), GRID_3D, jnp.float32,
             _depth(backend), backend=backend)


@pytest.mark.parametrize("backend", STRIP_BACKENDS)
def test_remainder_width_compiles(one_chip, backend):
    """W=300: a final column chunk narrower than the 128-wide band."""
    _compile(one_chip, StencilSpec("star", 2, 1), (2048, 300), jnp.float32,
             _depth(backend), backend=backend)


@pytest.mark.parametrize("backend", ["direct", "fused_matmul_reuse"])
def test_auto_column_tiled_compiles(one_chip, backend):
    """64x65536: full-width strips exceed the VMEM budget, so auto sizing
    column-tiles, with lane-aligned tiles and blocks."""
    halo = _depth(backend)
    g = resolve_substrate_geom((64, 65536), halo, 4)
    assert g.w_tile % 128 == 0 and g.w_block % 128 == 0 and g.w_tile > 0
    _compile(one_chip, StencilSpec("star", 2, 1), (64, 65536), jnp.float32,
             halo, backend=backend)


@pytest.mark.parametrize("backend", ["fused_direct", "fused_matmul_reuse"])
def test_reflect_plan_compiles(one_chip, backend):
    """Reflect fills mirror static slices in kernel (no ``rev``)."""
    _compile(one_chip, StencilSpec("box", 2, 1), (2048, 2048), jnp.float32,
             2, backend=backend, boundary="reflect")


@pytest.mark.parametrize("backend", ["fused_direct", "fused_matmul_reuse"])
def test_vmap_batch_fold_compiles(one_chip, backend):
    plan, _ = _compile(one_chip, StencilSpec("star", 2, 1), (256, 256),
                       jnp.float32, 2, backend=backend, batch=8,
                       batch_mode="vmap")
    assert plan.batch_mode == "vmap"


@pytest.mark.parametrize("grid,dtype_bytes,halo", [
    ((10240, 10240), 4, 1), ((10240, 10240), 4, 4), ((10240, 10240), 2, 2),
    ((512, 512, 512), 4, 2), ((2048, 300), 4, 2), ((64, 65536), 4, 1),
    ((20, 64), 4, 1), ((10, 20, 24), 4, 2), ((10248, 10242), 4, 1),
])
def test_auto_geometry_obeys_tiling_rule(grid, dtype_bytes, halo):
    """Auto sizing never emits a block Mosaic refuses (no topology
    needed: the rule is checked on the resolved geometry)."""
    g = resolve_substrate_geom(grid, halo, dtype_bytes)
    check_tpu_tiling(grid, g, dtype_bytes)


def test_pinned_illegal_geometry_raises_only_when_compiling():
    w = make_weights(StencilSpec("box", 2, 1), seed=0)
    with pytest.raises(ValueError, match="tiling rule.*h_block=2"):
        stencil_plan(w, (64, 64), np.float32, 1, backend="direct",
                     tile_m=16, h_block=2, interpret=False, use_cache=False)
    with pytest.raises(ValueError, match="w_tile=32"):
        stencil_plan(w, (64, 512), np.float32, 1, backend="direct",
                     tile_m=16, h_block=8, w_tile=32, interpret=False,
                     use_cache=False)
    plan = stencil_plan(w, (64, 64), np.float32, 1, backend="direct",
                        tile_m=16, h_block=2, interpret=True, use_cache=False)
    assert plan.interpret is True


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in its
    equations' parameters (kernel bodies, branches), depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for item in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(item, "jaxpr", item)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _primitives(jaxpr, out):
    out.update(eqn.primitive.name for eqn in _eqns(jaxpr))
    return out


@pytest.mark.parametrize("backend", STRIP_BACKENDS)
def test_compiled_kernels_trace_no_unlowerable_primitive(backend):
    """Traced for the chip (interpret=False), no kernel body holds a
    primitive Mosaic cannot lower: ``rev`` (reflect fills) or
    ``optimization_barrier`` (kept for interpret mode only)."""
    w = make_weights(StencilSpec("box", 2, 1), seed=0)
    plan = stencil_plan(w, (64, 256), np.float32, _depth(backend),
                        backend=backend, boundary="reflect"
                        if backend != "fused_matmul" else None,
                        interpret=False, use_cache=False)
    closed = jax.make_jaxpr(plan.fn)(
        jax.ShapeDtypeStruct((64, 256), jnp.float32))
    prims = _primitives(closed.jaxpr, set())
    assert "pallas_call" in prims
    assert not prims & {"rev", "optimization_barrier"}, prims


@pytest.mark.parametrize("backend, pins", [
    *[pytest.param(b, {}, id=b) for b in STRIP_BACKENDS],
    *[pytest.param(b, WHOLE_STRIP, id=f"{b}_wholestrip")
      for b in STRIP_BACKENDS[:5]]])
def test_kernel_is_named_after_its_backend(one_chip, backend, pins):
    """Every custom call of the compiled plan carries the backend's name
    (``%fused_direct.1 = ... custom-call``), so the profiler trace and
    the benchmark's breakdown name each kernel by its backend -- on the
    auto ring and on the whole-strip pin alike."""
    _, text = _compile(one_chip, StencilSpec("box", 2, 1), (512, 1024),
                       jnp.float32, _depth(backend), backend=backend,
                       **pins)
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    assert calls
    for line in calls:
        assert re.match(rf"\s*(ROOT )?%{backend}(\.\d+)? = ", line), line


#: The serialized Mosaic body of each kernel in a lowered program.
_BODY = re.compile(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22")
SCOPES = (trace.SUBSTRATE_ASSEMBLE.encode(), trace.KERNEL_COMPUTE.encode())


def _scoped_plan(one_chip, backend, spec, grid, t=2):
    """A compiled-mode plan and the Mosaic bodies of its kernels."""
    w = make_weights(spec, seed=0)
    plan = stencil_plan(w, grid, jnp.float32, t, backend=backend,
                        interpret=False, use_cache=False)
    arg = jax.ShapeDtypeStruct(grid, jnp.float32, sharding=one_chip)
    lowered = plan.fn.lower(arg)
    bodies = [base64.b64decode(b) for b in _BODY.findall(lowered.as_text())]
    assert bodies, "no Mosaic body in the lowered program"
    return plan, bodies, lowered


@pytest.mark.parametrize("backend, spec, grid", [
    ("fused_direct", StencilSpec("box", 2, 1), (512, 1024)),
    # 1D: the scratch-free "flat" launch
    ("fused_direct", StencilSpec("box", 1, 1), (4096,)),
    ("fused_matmul_reuse", StencilSpec("star", 3, 1), (32, 64, 256)),
])
def test_kernel_scopes_compile_in_only_when_on(one_chip, backend, spec,
                                               grid):
    """Off, the kernel is byte-identical to one built before the switch
    was touched and holds no trace op; on, it holds both scopes (as
    ``tpu.trace_start``) and compiles; the two plans never share a cache
    key."""
    assert trace.kernel_scopes() is False
    built = []
    # One call site for all three builds: the bodies carry source
    # locations, which must not differ for another reason.
    for switch in (contextlib.nullcontext(), trace.kernel_scopes_on(),
                   contextlib.nullcontext()):
        with switch:
            built.append(_scoped_plan(one_chip, backend, spec, grid))
    (plain, before, _), (scoped, on, lowered), (again, after, _) = built
    assert after == before
    for body in before:
        assert b"trace_start" not in body
        assert not any(name in body for name in SCOPES)
    for body in on:
        assert b"trace_start" in body
        assert all(name in body for name in SCOPES)
    assert scoped.key != plain.key and again.key == plain.key
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("grid", [GRID_3D, (1024, 1024, 1024)],
                         ids=["512^3", "1024^3"])
def test_star3d_f32_auto_plan_compiles_on_the_vpu(one_chip, grid):
    """Star-3D1R f32 at t=2 (the 3D step cell's shape): an f32 contraction
    takes six bf16 MXU passes, so the auto plan runs the VPU's
    ``fused_direct`` -- by a margin, not a rounding tie -- and compiles
    within the scoped VMEM limit at the priced ring's geometry."""
    plan, text = _compile(one_chip, StencilSpec("star", 3, 1), grid,
                          jnp.float32, 2)
    assert plan.backend == "fused_direct"
    assert plan.decision.candidates["fused_matmul_reuse"] * 1.5 < \
        plan.decision.candidates["fused_direct"]
    assert "fused_direct" in text


def test_box2d3r_f32_auto_plan_compiles_direct(one_chip):
    """Box-2D3R (49 taps) f32 at 10240^2, t=1 (the high-radius box cell's
    shape): the auto plan runs the unfused VPU kernel ``direct`` on the
    2D strip substrate and compiles at the published width."""
    plan, text = _compile(one_chip, StencilSpec("box", 2, 3), GRID_2D,
                          jnp.float32, 1)
    assert plan.backend == "direct"
    assert "Eq. 16" in plan.decision.reason
    assert "strip_m=32, h_block=8" in plan.decision.reason
    assert re.search(r"%direct(\.\d+)? = ", text)


@pytest.mark.parametrize("spec, t", [(StencilSpec("box", 2, 1), 4),
                                     (StencilSpec("box", 2, 3), 1)],
                         ids=["box2d1r-t4", "box2d3r-t1"])
def test_box_auto_plans_lower_with_a_lane_rotate(one_chip, spec, t):
    """The box cells' auto plans at 10240^2 form their column operands by
    lane rotates: the Mosaic module holds a rotate, and the decision
    record says ``roll``."""
    plan, bodies, _ = _scoped_plan(one_chip, None, spec, GRID_2D, t=t)
    assert plan.backend in ("direct", "fused_direct")
    assert plan.decision.reason.endswith("| column shifts=roll")
    assert all(b"rotate" in body for body in bodies)


def test_2x2_local_block_compiles_rolled(one_chip):
    """The 2x2 cell's local kernel: a 10248^2 halo-extended block (not a
    multiple of 128 wide), ``fused_direct`` at t=4, rolled, compiles."""
    plan, text = _compile(one_chip, StencilSpec("box", 2, 1), (10248, 10248),
                          jnp.float32, 4, backend="fused_direct")
    assert plan.decision.reason.endswith("| column shifts=roll")
    assert re.search(r"%fused_direct(\.\d+)? = ", text)


def test_vmap_batch_fold_compiles_rolled(one_chip):
    """A box batch fold under ``vmap``: the rolled kernel body compiles
    with the batch grid axis in front."""
    plan, _ = _compile(one_chip, StencilSpec("box", 2, 1), (256, 256),
                       jnp.float32, 2, backend="fused_direct", batch=8,
                       batch_mode="vmap")
    assert plan.batch_mode == "vmap"
