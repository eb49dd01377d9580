"""Lane-rotated column operands of the VPU tap sum (``stencil_direct``).

On a full-width substrate with a periodic last axis, a box stencil's
column offsets each feed several taps, so the kernel forms each offset's
operand by one ``pltpu.roll`` of the region instead of slicing the
wrapped (n+2r)-wide columns at every tap.  The values and the tap order
are unchanged, so the output stays bitwise the oracle's; every other
kernel keeps the slice form.  Kernels run in interpret mode, where each
rotated operand is taken as the wrapped-column slice it equals (XLA:CPU
contracts FMAs by buffer layout); the rotate itself is checked against
that slice, and the kernels traced for the chip are walked for it.
"""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import audit
from repro.kernels import explain, registry, stencil_plan
from repro.kernels.stencil_direct import _rolled_columns, column_shift_form
from repro.stencil import StencilSpec, make_weights
from repro.stencil.reference import apply_stencil_steps
from test_tpu_compile import _eqns


def _x(shape, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=shape)
                       .astype(np.float32))


@pytest.mark.parametrize("spec, grid, t, backend, pins", [
    (("box", 2, 1), (64, 256), 1, "direct", {}),
    (("box", 2, 1), (64, 256), 4, "fused_direct", {}),
    (("box", 2, 3), (64, 256), 1, "direct", {}),
    (("box", 3, 1), (16, 16, 128), 2, "fused_direct", {}),
    # the 2x2 cell's local block (10240 + 8 wide), scaled down
    (("box", 2, 1), (264, 264), 4, "fused_direct", {}),
    # the ring pinned to whole neighbor strips
    (("box", 2, 1), (64, 256), 2, "fused_direct",
     {"tile_m": 32, "h_block": 32}),
], ids=["box2d1r-t1", "box2d1r-t4", "box2d3r-t1", "box3d1r-t2",
        "width264-t4", "wholestrip-t2"])
def test_rolled_kernel_is_bitwise_the_oracle(spec, grid, t, backend, pins):
    w = make_weights(StencilSpec(*spec), seed=1)
    plan = stencil_plan(w, grid, np.float32, t, backend=backend,
                        interpret=True, use_cache=False, **pins)
    assert plan.decision.reason.endswith("| column shifts=roll")
    x = _x(grid)
    y = plan(x)
    ref = apply_stencil_steps(x, jnp.asarray(w), t, "periodic")
    assert np.array_equal(np.asarray(y), np.asarray(ref))


@pytest.mark.parametrize("spec, shape", [
    (("box", 2, 1), (10, 256)), (("box", 2, 3), (14, 200)),
    (("box", 3, 1), (6, 6, 128)), (("box", 2, 2), (12, 8))],
    ids=["box2d1r", "box2d3r-width200", "box3d1r", "box2d2r-width8"])
def test_rotated_operands_equal_the_wrapped_slices(spec, shape):
    """Each column offset's ``pltpu.roll`` operand (the compiled form,
    run through its generic lowering) is exactly the wrapped-column
    slice interpret mode takes: the shift ``(r - j) % n`` is right."""
    w = make_weights(StencilSpec(*spec), seed=1)
    lead = tuple(m - (k - 1) for m, k in zip(shape[:-1], w.shape[:-1]))
    cur = _x(shape, seed=4)
    rolled = jax.jit(lambda c: {j: o for j, (o, _) in
                                _rolled_columns(c, w, lead).items()})(cur)
    sliced = _rolled_columns(cur, w, lead, interpret=True)
    assert sorted(rolled) == sorted(sliced) == list(range(w.shape[-1]))
    for j, (operand, lo) in sliced.items():
        assert lo == (0,) * len(lead)
        assert np.array_equal(np.asarray(rolled[j]), np.asarray(operand))


def _kernel_counts(spec, grid, t, backend, **kw):
    """Per ``pallas_call`` of a plan traced for the chip: (rolls,
    last-axis concatenates) in its kernel body."""
    w = make_weights(StencilSpec(*spec), seed=1)
    plan = stencil_plan(w, grid, np.float32, t, backend=backend,
                        interpret=False, use_cache=False, **kw)
    closed = jax.make_jaxpr(plan.fn)(
        jax.ShapeDtypeStruct(grid, jnp.float32))
    counts = []
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        body = collections.Counter()
        for e in _eqns(eqn.params["jaxpr"]):
            if e.primitive.name == "roll":
                body["roll"] += 1
            elif (e.primitive.name == "concatenate"
                  and e.params["dimension"] == e.outvars[0].aval.ndim - 1):
                body["concat_x"] += 1
        counts.append((body["roll"], body["concat_x"]))
    assert counts, "no kernel in the plan"
    return counts


@pytest.mark.parametrize("spec, grid, t, backend, steps, pins", [
    (("box", 2, 1), (64, 256), 4, "fused_direct", 4, {}),
    (("box", 2, 1), (64, 256), 2, "direct", 1, {}),
    (("box", 2, 3), (64, 256), 1, "direct", 1, {}),
    (("box", 3, 1), (16, 16, 128), 2, "fused_direct", 2, {}),
    (("box", 2, 1), (64, 256), 2, "fused_direct", 2,
     {"tile_m": 32, "h_block": 32}),
], ids=["box2d1r-fused-t4", "box2d1r-direct-t2", "box2d3r", "box3d1r",
        "wholestrip"])
def test_box_kernels_trace_2r_rolls_per_step(spec, grid, t, backend, steps,
                                             pins):
    """2r rolls per fused step of each kernel, no last-axis concat."""
    for rolls, concat_x in _kernel_counts(spec, grid, t, backend, **pins):
        assert rolls == 2 * spec[2] * steps
        assert concat_x == 0


@pytest.mark.parametrize("spec, grid, kw", [
    (("star", 2, 1), (64, 256), {}),
    (("star", 3, 1), (16, 16, 128), {}),
    (("box", 1, 2), (512,), {}),
    (("box", 2, 1), (64, 256), {"boundary": "reflect"}),
    (("box", 2, 1), (64, 256), {"boundary": ("periodic", "zero")}),
    (("box", 2, 1), (64, 1024), {"w_tile": 256, "w_block": 128}),
], ids=["star2d", "star3d", "lifted1d", "reflect", "zero-x",
        "column-tiled"])
def test_other_kernels_keep_the_slice_form(spec, grid, kw):
    """Star stencils, the lifted 1D kernel, non-periodic last axes and the
    column-tiled substrate trace no roll."""
    for rolls, _ in _kernel_counts(spec, grid, 2, "fused_direct", **kw):
        assert rolls == 0


@pytest.mark.parametrize("spec, grid, t, kw, form", [
    (("box", 2, 1), (10240, 10240), 4, {}, "roll"),
    (("box", 2, 3), (10240, 10240), 1, {}, "roll"),
    (("box", 2, 1), (10248, 10248), 4, {}, "roll"),
    (("box", 3, 1), (512, 512, 512), 2, {}, "roll"),
    (("star", 3, 1), (1024, 1024, 1024), 2, {}, "slice"),
    (("star", 2, 2), (2048, 2048), 2, {}, "slice"),
    (("box", 1, 2), (4096,), 2, {}, "slice"),
    (("box", 2, 1), (2048, 2048), 2, {"boundary": "reflect"}, "slice"),
    (("box", 2, 1), (64, 1024), 2, {"w_tile": 256, "w_block": 128},
     "slice"),
], ids=["box2d1r-cell", "box2d3r-cell", "2x2-local", "box3d1r",
        "star3d1r-cell", "star2d2r", "lifted1d", "reflect", "column-tiled"])
def test_decision_records_the_column_shift_form(spec, grid, t, kw, form):
    """``explain`` (the plan's decision path) quotes the form the kernel
    traces for every VPU pick, and no form for an MXU pick."""
    w = make_weights(StencilSpec(*spec), seed=1)
    d = explain(w, t, grid_shape=grid, **kw)
    assert d.backend in ("direct", "fused_direct")
    assert d.reason.endswith(f"| column shifts={form}")
    mode_x = kw.get("boundary", "periodic")
    assert column_shift_form(w, mode_x if isinstance(mode_x, str)
                             else mode_x[-1],
                             wrap_x="w_tile" not in kw) == form


def test_mxu_decision_quotes_no_column_shift_form():
    w = make_weights(StencilSpec("star", 2, 1), seed=1)
    d = explain(w, 2, grid_shape=(256, 512), tile_n=32,
                use_sparse_unit=True)
    assert registry.candidate_units()[d.backend] == "matrix"
    assert "column shifts=" not in d.reason


def test_column_shift_form_reads_the_tap_pattern():
    """Two nonzero taps on one off-centre column engage the roll, whatever
    the stencil's name; one tap per off-centre column does not."""
    w = np.zeros((3, 3), np.float32)
    w[1, :] = 1.0
    assert column_shift_form(w) == "slice"
    w[0, 0] = w[2, 0] = 1.0
    assert column_shift_form(w) == "roll"
    assert column_shift_form(w, "replicate") == "slice"
    assert column_shift_form(w, wrap_x=False) == "slice"


@pytest.mark.parametrize("backend", ["direct", "fused_direct"])
def test_roll_is_flop_free_in_the_audit(backend):
    """The auditor's jaxpr FLOP count still equals the tap-sum mirror
    exactly on a rolled kernel: a rotate adds no arithmetic."""
    spec = StencilSpec("box", 2, 2)
    ctx = registry.PlanContext(
        spec=spec, weights=make_weights(spec, seed=2), grid_shape=(64, 512),
        dtype=np.dtype(np.float32), t=2, tile_m=None, tile_n=None,
        interpret=True)
    rep = audit.audit_context(ctx, backend)
    assert rep.ok, rep.summary()
    (c,) = [c for c in rep.checks if c.name == "flops/structural"]
    assert c.expected == c.actual


def test_partial_column_windows_stay_bitwise():
    """Off-centre columns whose taps read only some rows roll just the
    rows those taps read (the union of their windows).  With zero taps
    inside the support the oracle contracts its sums otherwise, in the
    last ulp, for the slice form as for the roll: compare by value."""
    w = np.zeros((5, 5), np.float32)
    w[2, :] = [0.1, 0.2, 0.3, 0.2, 0.1]
    w[0, 4] = w[1, 4] = 0.05      # column 4 read by rows 0-1 only
    w[3, 0] = w[4, 0] = -0.05     # column 0 read by rows 3-4 only
    assert column_shift_form(w) == "roll"
    x = _x((64, 256), seed=3)
    plan = stencil_plan(w, x.shape, np.float32, 2, backend="fused_direct",
                        interpret=True, use_cache=False)
    ref = apply_stencil_steps(x, jnp.asarray(w), 2, "periodic")
    np.testing.assert_allclose(np.asarray(plan(x)), np.asarray(ref),
                               rtol=0, atol=1e-6)
