"""3D slab sizing (DESIGN.md §9): ``choose_slab_blocks`` prices each
call's ring walk -- ring read bytes plus grid steps at ``STEP_BYTES``
each -- over slab, strip and both block heights; ``SubstrateGeom
.grid_steps`` counts the launch grid it sized; the 2D strip sizing is
left as it was."""
import itertools
import math

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import stencil_plan
from repro.kernels.common import (LANE, STEP_BYTES, VMEM_BUDGET_BYTES,
                                  SubstrateGeom, _slab_working_set,
                                  choose_slab_blocks, choose_strip_blocks,
                                  hbm_read_bytes_per_step_3d,
                                  launch_geometry, resolve_substrate_geom,
                                  slab_walk_cost, substrate_grid_steps)
from repro.stencil import StencilSpec, make_weights

#: The pick before steps were priced (least read amplification, block
#: heights the smallest halo-covering divisors): the Star-3D cell's.
OLD_1024_HALO2 = SubstrateGeom(dim=3, strip_m=32, h_block=8, z_slab=4,
                               z_block=2)


def _geom(zs, zb, sm, hb):
    return SubstrateGeom(dim=3, strip_m=sm, h_block=hb, z_slab=zs,
                         z_block=zb)


def _divisors(n, lo, align=1, cap=None):
    return [d for d in range(1, n + 1)
            if n % d == 0 and d >= lo and (cap is None or d <= cap)
            and (d % align == 0 or (cap is not None and d == n))]


def _brute_force(grid, halo):
    """Every full-width geometry of the search space, with its priced
    cost computed from the two public models, and whether it fits the
    budget."""
    z, h, n = grid
    out = []
    for zs in _divisors(z, halo, cap=128):
        for sm in _divisors(h, halo, 8, cap=128):
            hbs = [d for d in _divisors(sm, halo, 8) if d % 8 == 0] or [sm]
            for zb, hb in itertools.product(_divisors(zs, halo), hbs):
                g = _geom(zs, zb, sm, hb)
                cost = (hbm_read_bytes_per_step_3d(grid, g, 4)
                        + substrate_grid_steps(grid, g) * STEP_BYTES)
                fits = _slab_working_set(g, n, halo, halo, 4) \
                    <= VMEM_BUDGET_BYTES
                out.append((g, cost, fits))
    return out


def test_star3d_1024_walk_takes_at_most_half_the_old_steps():
    """The Star-3D cell's grid (1024^3 f32, fused halo 2): the priced
    walk takes at most 98,304 grid steps, half of the 196,608 the least
    read amplification took, within the VMEM budget, at full width."""
    grid = (1024, 1024, 1024)
    g = resolve_substrate_geom(grid, 2, 4)
    assert g.w_tile == 0
    assert substrate_grid_steps(grid, OLD_1024_HALO2) == 196_608
    assert g.grid_steps <= 98_304
    assert _slab_working_set(g, 1024, 2, 2, 4) <= VMEM_BUDGET_BYTES
    assert slab_walk_cost(grid, g, 4) < \
        slab_walk_cost(grid, OLD_1024_HALO2, 4)
    assert f"grid_steps={g.grid_steps}" in g.describe()


@pytest.mark.parametrize("halo", [1, 2, 3])
@pytest.mark.parametrize("grid", [(1024, 1024, 1024), (64, 256, 512),
                                  (128, 128, 128)],
                         ids=["1024^3", "64x256x512", "128^3"])
def test_pick_minimises_the_priced_walk(grid, halo):
    """Brute force over every (z_slab, z_block, strip_m, h_block) that
    fits: no fitting geometry walks for less than the pick."""
    zs, zb, sm, hb, wt, wb = choose_slab_blocks(*grid, halo)
    assert (wt, wb) == (0, 0)
    pick = _geom(zs, zb, sm, hb)
    every = _brute_force(grid, halo)
    fitting = [cost for _, cost, fits in every if fits]
    assert pick in [g for g, _, fits in every if fits]
    assert slab_walk_cost(grid, pick, 4) == min(fitting)


@pytest.mark.parametrize("h,n,halo,want", [
    (10240, 10240, 4, (32, 8)), (10248, 10248, 4, (24, 8)),
    (10240, 10240, 3, (32, 8))])
def test_2d_strip_picks_unchanged(h, n, halo, want):
    """The 2D sizing is its own rule and keeps its picks: the box cells'
    grids resolve as before."""
    assert choose_strip_blocks(h, n, halo) == want


@pytest.mark.parametrize("boundary", ["periodic", "reflect"])
@pytest.mark.parametrize("shape", ["star", "box"])
def test_auto_plan_bitwise_equal_to_old_geometry(shape, boundary):
    """An interpret-mode auto 3D plan (t=2) and the same plan pinned to
    the geometry the least-read-amplification rule picked for its grid
    return the same bits: the walk changes, the assembled slabs do not."""
    grid = (32, 64, 128)
    w = make_weights(StencilSpec(shape, 3, 1), seed=3)
    x = jnp.asarray(np.random.default_rng(7).normal(size=grid)
                    .astype(np.float32))
    auto = stencil_plan(w, grid, jnp.float32, 2, backend="fused_direct",
                        boundary=boundary, interpret=True, use_cache=False)
    old = stencil_plan(w, grid, jnp.float32, 2, backend="fused_direct",
                       boundary=boundary, interpret=True, use_cache=False,
                       z_slab=16, z_block=2, tile_m=64, h_block=8, w_tile=0)
    new_geom = auto.ctx.resolve_geom(2)
    assert (new_geom.z_block, new_geom.h_block) != (2, 8)
    np.testing.assert_array_equal(np.asarray(auto(x)), np.asarray(old(x)))


@pytest.mark.parametrize("grid,halo,pins", [
    ((1024, 1024, 1024), 2, {}),
    ((32, 64, 128), 1, {}),
    ((10, 20, 24), 2, {}),
    ((16, 64, 1024), 2, {"w_tile": 256}),
    ((16, 64, 1000), 2, {"w_tile": 384}),
    ((8, 64, 40000), 1, {}),
    ((256, 512), 2, {}),
    ((64, 1024), 2, {"w_tile": 256}),
    ((96,), 2, {}),
])
def test_grid_steps_equals_launch_grid(grid, halo, pins):
    """``SubstrateGeom.grid_steps`` is the size of the grid the
    substrate launches for that geometry, on every rank and width
    path."""
    g = resolve_substrate_geom(grid, halo, 4, **pins)
    lg = launch_geometry(grid, g, halo, x_halo=halo)
    assert g.grid_steps == math.prod(lg.grid) > 0
    if len(grid) == 3 and "w_tile" not in pins and grid[-1] > 20_000:
        assert g.w_tile and g.w_tile % LANE == 0    # column-tiled auto


def test_block_pins_condition_the_search():
    """Pinned block heights are kept and the free axes are sized around
    them; the pinned geometry that the least-read-amplification sizing
    sent to column tiling stays at full width."""
    g = resolve_substrate_geom((1024, 1024, 1024), 2, 4, z_slab=8,
                               z_block=4, tile_m=32, h_block=8)
    assert (g.z_slab, g.z_block, g.strip_m, g.h_block, g.w_tile) == \
        (8, 4, 32, 8, 0)
    g = resolve_substrate_geom((1024, 1024, 1024), 2, 4, h_block=16)
    assert g.h_block == 16 and g.strip_m % 16 == 0
