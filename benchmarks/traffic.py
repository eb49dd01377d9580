"""Substrate HBM-traffic benchmark: the block-ring substrate at its auto
halo blocks vs the same ring pinned to whole neighbor strips.

The paper's whole argument is that stencils are memory-bound (I = K/D,
Eq. 6), so the substrate's HBM traffic model IS the experiment.  Every
kernel runs on one substrate (DESIGN.md §3): a ring of blocks per output
strip, each strip's own h-row blocks plus ONE h-block per vertical
neighbor (1 + 2h/strip_m, ~1.1-1.25x at the benchmark strips), with the
horizontal periodic halo materialized in-VMEM for free.  Pinned to
``h_block = strip_m`` the same ring reads three full strips per strip
(3x, the whole-strip read pattern), and in 3D with ``z_block = z_slab``
too, nine full slabs (9x, the whole-slab pattern); the
``*_wholestrip`` columns are that pin's analytic read model.

For Box/Star x r in {1,2,3} x t in {1,2,4,8} this emits:
  * block loads issued per output strip (strip_m/h + 2 vs 3 for the
    pin, analytic from the BlockSpec structure),
  * per-step HBM read bytes (analytic, including the banded operand on the
    MXU paths) -- the ``read_bytes_step_*_subblocked`` columns show the
    amplification at 1.125-1.25x for shallow halos (halo <= strip_m/8,
    the whole BENCH_QUICK sweep), climbing toward the pin's 3.0x only
    where t*r approaches the 32-row strip height,
  * measured us/step of the Pallas kernels (interpret mode on CPU -- honest
    relative numbers, labeled as such), VPU ``fused_direct`` and MXU
    ``fused_matmul_reuse``, executed through compiled ``stencil_plan``
    objects so per-trial timing excludes selection, tile sizing and
    weight composition -- plan-build time is recorded separately
    (``plan_build_us_*`` in the JSON).

The 3D halo-plane substrate (DESIGN.md §9) gets its own sweep
(``cases_3d``): Box/Star-3D x r{1,2} x t{1,2} at fixed benchmark slab
sizes, the auto halo-plane blocks ((1 + 2h/strip_m)(1 + 2z_block/z_slab)x)
vs the whole-slab pin (9x), with analytic
``read_bytes_step_*_{wholestrip,subblocked}`` columns and plan-timed
us/step for the VPU and intermediate-reuse MXU paths.

The sparse-compacted MXU regime (DESIGN.md §14) rides every 2D/3D case:
``band_sparsity`` / ``kept_row_fraction`` quantify the star-vs-box
structural sparsity of the banded operand, ``mxu_flops_step_sparse`` vs
``mxu_flops_step_dense`` show the compacted contraction executing exactly
S * dense MXU FLOPs (star < dense, box == dense), and
``us_step_matmul_sparse`` / ``sparse_bitwise_equal`` time the
``fused_sparse_matmul`` plan and prove its output bit-identical to the
dense reuse plan -- ``scripts/verify.sh`` gates on both.

The column-tiled W substrate (DESIGN.md §10) gets the wide-grid sweep
(``cases_wide``): a grid whose FULL-WIDTH strips exceed the VMEM budget
(REPRO_VMEM_BUDGET pinned for the case, so the auto sizing genuinely
escalates), the column-tiled substrate
((1 + 2h/strip_m)(1 + 2w_block/w_tile)x) against the whole-width
whole-strip pin's analytic 3x, with the resolved (w_tile, w_block)
recorded and ``scripts/verify.sh`` asserting the column-tiled
amplification stays below the pin's.

Per-axis boundary modes (DESIGN.md §15) ride the sweep two ways: every
row carries a ``boundary`` column (the other sweeps are all-periodic,
the ``cases_boundary`` sweep times the sub-blocked VPU/MXU plans under
zero/reflect/replicate/mixed specs with a mode-matched oracle check),
and ``halo_overlap`` records the distributed overlap-vs-serialized
timing pair: a 2-device subprocess times the ``overlap`` stepper (one
dispatch, interior concurrent with the exchange) against the
serialized-exchange foil (per step: exchange dispatch, host sync,
compute dispatch -- the execution a runtime without overlap pays),
bitwise-equal outputs, with the trace-time interleave counters
(``interior_before_recv_consumed``) proving the interior launch never
waited on a recv.

Results also land in BENCH_kernels.json (repo root) for cross-PR
trajectory tracking.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks.timing import CaseTimeout, case_budget, time_us
from repro.core import events as guard_events
from repro.kernels import common, plan_cache_stats, stencil_plan
from repro.kernels.common import (SubstrateGeom, choose_hblock,
                                  hbm_read_bytes_per_step_3d,
                                  resolve_substrate_geom,
                                  substrate_read_amp)
from repro.kernels.stencil_matmul import (band_sparsity, build_bands,
                                          build_bands_nd)
from repro.kernels.stencil_sparse import compact_bands, kept_row_fraction
from repro.stencil import StencilSpec, make_weights
from repro.stencil.boundary import boundary_label, resolve_boundary
from repro.stencil.reference import apply_stencil_steps

N = 128            # grid edge (small: interpret-mode kernels on CPU)
TILE = 32          # strip height of the 2D sweep
SHAPES = ("box", "star")
RADII = (1, 2, 3)
DEPTHS = (1, 2, 4, 8)
#: BENCH_QUICK=1 trims the sweep (CI / verify.sh); default is the full
#: Box/Star x r{1,2,3} x t{1,2,4,8} grid of the ISSUE.
QUICK_RADII = (1,)
QUICK_DEPTHS = (1, 4)
DTYPE_BYTES = 4
#: 3D halo-plane substrate sweep (DESIGN.md §9): Box/Star-3D at the
#: paper's Table 3 workloads, the whole-slab pin's analytic 9x vs the
#: auto halo-plane blocks ((1 + 2h/strip_m)(1 + 2z_block/z_slab)x).
#: Small grid + fixed (z_slab, strip_m) so interpret-mode timing stays
#: honest and the analytic amplification is exact at the benchmark slab
#: sizes.
N3 = (16, 32, 32)      # (Z, H, W)
SLAB3, STRIP3, TILE3 = 8, 16, 32
CASES_3D = [(s, r, t) for s in SHAPES for r in (1, 2) for t in (1, 2)]
QUICK_CASES_3D = [("box", 1, 2), ("star", 1, 2)]
#: Wide-grid column-tiled sweep (DESIGN.md §10): a width whose FULL-WIDTH
#: strip working set exceeds the VMEM budget, so auto resolution
#: column-tiles W.  The default 8 MB budget would need W in the hundreds
#: of thousands -- far beyond honest interpret-mode timing -- so the case
#: pins REPRO_VMEM_BUDGET (the satellite's env override, folded into plan
#: cache keys) to a budget the benchmark width genuinely exceeds.
N_WIDE = (32, 1024)    # (H, W): full-width needs >= ~66 KB at t=2
WIDE_BUDGET = 16 * 1024
CASES_WIDE = [("box", 1, 1), ("box", 1, 2), ("star", 1, 2)]
QUICK_CASES_WIDE = [("box", 1, 2)]
#: Full sweeps land in BENCH_kernels.json (the cross-PR trajectory file);
#: BENCH_QUICK=1 sweeps go to a sibling .quick file so CI smoke runs never
#: clobber tracked full-grid data.
JSON_PATH = os.path.join(os.path.dirname(__file__), "..", "BENCH_kernels.json")
JSON_PATH_QUICK = os.path.join(os.path.dirname(__file__), "..",
                               "BENCH_kernels.quick.json")


def _mxu_step_flops(w, tile_n: int, width: int, m_rows: int):
    """(dense, sparse) per-step MXU FLOPs of the radius-r banded
    contraction over the grid: the kernels' exact chunk walk, with
    full-width chunks compacted to the packed band rows and remainder
    chunks dense (DESIGN.md §14).  On tile-aligned widths
    sparse == kept_row_fraction * dense, integer-exact -- the same
    identity ``repro.audit``'s ``flops/sparse-compaction`` proves on the
    traced jaxpr."""
    offsets, bands = build_bands_nd(np.asarray(w, dtype=np.float32), tile_n)
    _, packed = compact_bands(offsets, bands)
    r = (bands.shape[1] - bands.shape[2]) // 2
    dense = sparse = 0
    start = 0
    while start < width:
        wcur = min(tile_n, width - start)
        d = len(offsets) * 2 * m_rows * wcur * (wcur + 2 * r)
        dense += d
        sparse += 2 * m_rows * wcur * packed.shape[0] \
            if wcur == tile_n else d
        start += wcur
    return dense, sparse


def _case(shape: str, r: int, t: int, x) -> dict:
    spec = StencilSpec(shape, 2, r)
    w = make_weights(spec, seed=r)
    halo = r * t                      # fused-regime vertical halo at TILE strips
    hb = choose_hblock(TILE, halo)
    bands = build_bands(w.astype(np.float32), TILE).shape

    def reads(h_block, bands_shape=None):
        # one fused launch advances t steps: per-step read traffic
        return common.hbm_read_bytes_per_step(
            (N, N), TILE, DTYPE_BYTES, bands_shape=bands_shape,
            h_block=h_block) / t

    row = {
        "case": f"{spec.name}-t{t}", "shape": shape, "r": r, "t": t,
        "boundary": "periodic",
        "loads_per_tile_wholestrip": TILE // TILE + 2,
        "loads_per_tile_subblocked": TILE // hb + 2,
        "h_block": hb,
        "read_amp_wholestrip": substrate_read_amp(TILE, TILE),
        "read_amp_subblocked": substrate_read_amp(TILE, hb),
        "read_bytes_step_direct_wholestrip": reads(TILE),
        "read_bytes_step_direct_subblocked": reads(hb),
        "read_bytes_step_matmul_wholestrip": reads(TILE, bands),
        "read_bytes_step_matmul_subblocked": reads(hb, bands),
    }
    # Star-vs-box sparsity sweep (DESIGN.md §14): element sparsity of the
    # banded operand, the achievable kept-row fraction S, and the per-step
    # MXU FLOPs with/without compaction (sparse == S * dense on this
    # tile-aligned width; star keeps only its tap rows, box keeps all).
    dense_f, sparse_f = _mxu_step_flops(w, TILE, N, N)
    row["band_sparsity"] = band_sparsity(w.astype(np.float32), TILE)
    row["kept_row_fraction"] = kept_row_fraction(w, TILE)
    row["mxu_flops_step_dense"] = dense_f
    row["mxu_flops_step_sparse"] = sparse_f

    # Execution goes through compiled plans: selection/sizing/weight
    # composition happen at build (accounted separately below), the plan's
    # jitted callable is what gets timed -- time_us's warmup still absorbs
    # trace+compile, so the timed iterations are steady-state execution with
    # zero re-analysis (the fused regimes degenerate to the plain kernels
    # at t=1).
    paths = {
        "us_step_direct_subblocked": stencil_plan(
            w, x.shape, x.dtype, t, backend="fused_direct",
            tile_m=TILE, h_block=hb, interpret=True),
        "us_step_matmul_subblocked": stencil_plan(
            w, x.shape, x.dtype, t, backend="fused_matmul_reuse",
            tile_m=TILE, tile_n=TILE, h_block=hb, interpret=True),
        # sparse-compacted MXU path, same substrate pins as the reuse plan
        "us_step_matmul_sparse": stencil_plan(
            w, x.shape, x.dtype, t, backend="fused_sparse_matmul",
            tile_m=TILE, tile_n=TILE, h_block=hb, interpret=True),
    }
    iters = 2 if os.environ.get("BENCH_QUICK") else 5
    for key, plan in paths.items():
        row[key] = time_us(plan, x, iters=iters) / t
        # host-side plan construction (selection + sizing + composition),
        # paid once per signature -- never part of the per-step numbers
        row[key.replace("us_step_", "plan_build_us_")] = \
            plan.build_time_s * 1e6
    row["sparse_bitwise_equal"] = bool(np.array_equal(
        np.asarray(paths["us_step_matmul_sparse"](x)),
        np.asarray(paths["us_step_matmul_subblocked"](x))))
    return row


def _case3d(shape: str, r: int, t: int, x3) -> dict:
    """One 3D traffic case: auto halo-plane blocks vs the whole-slab pin
    (analytic)."""
    spec = StencilSpec(shape, 3, r)
    w = make_weights(spec, seed=r)
    halo = r * t
    hb = choose_hblock(STRIP3, halo)
    zb = choose_hblock(SLAB3, halo, 1)          # z is a leading axis
    sub = SubstrateGeom(dim=3, strip_m=STRIP3, h_block=hb,
                        z_slab=SLAB3, z_block=zb)
    whole = SubstrateGeom(dim=3, strip_m=STRIP3, h_block=STRIP3,
                          z_slab=SLAB3, z_block=SLAB3)
    bands = build_bands_nd(w.astype(np.float32), TILE3)[1].shape

    row = {
        "case": f"{spec.name}-t{t}", "shape": shape, "dim": 3, "r": r, "t": t,
        "boundary": "periodic",
        "z_slab": SLAB3, "strip_m": STRIP3, "h_block": hb, "z_block": zb,
        "loads_per_cell_wholestrip": 3 * 3,
        "loads_per_cell_subblocked": (SLAB3 // zb + 2) * (STRIP3 // hb + 2),
        "read_amp_wholestrip": whole.read_amp,
        "read_amp_subblocked": sub.read_amp,
        # one fused launch advances t steps: per-step read traffic
        "read_bytes_step_direct_wholestrip": hbm_read_bytes_per_step_3d(
            N3, whole, DTYPE_BYTES) / t,
        "read_bytes_step_direct_subblocked": hbm_read_bytes_per_step_3d(
            N3, sub, DTYPE_BYTES) / t,
        "read_bytes_step_matmul_wholestrip": hbm_read_bytes_per_step_3d(
            N3, whole, DTYPE_BYTES, bands_shape=bands) / t,
        "read_bytes_step_matmul_subblocked": hbm_read_bytes_per_step_3d(
            N3, sub, DTYPE_BYTES, bands_shape=bands) / t,
    }
    dense_f, sparse_f = _mxu_step_flops(w, TILE3, N3[2], N3[0] * N3[1])
    row["band_sparsity"] = band_sparsity(w.astype(np.float32), TILE3)
    row["kept_row_fraction"] = kept_row_fraction(w, TILE3)
    row["mxu_flops_step_dense"] = dense_f
    row["mxu_flops_step_sparse"] = sparse_f

    pins = dict(tile_m=STRIP3, z_slab=SLAB3, interpret=True)
    paths = {
        "us_step_direct_subblocked": stencil_plan(
            w, N3, x3.dtype, t, backend="fused_direct",
            h_block=hb, z_block=zb, **pins),
        "us_step_matmul_subblocked": stencil_plan(
            w, N3, x3.dtype, t, backend="fused_matmul_reuse",
            tile_n=TILE3, h_block=hb, z_block=zb, **pins),
        "us_step_matmul_sparse": stencil_plan(
            w, N3, x3.dtype, t, backend="fused_sparse_matmul",
            tile_n=TILE3, h_block=hb, z_block=zb, **pins),
    }
    iters = 1 if os.environ.get("BENCH_QUICK") else 3
    for key, plan in paths.items():
        row[key] = time_us(plan, x3, iters=iters) / t
        row[key.replace("us_step_", "plan_build_us_")] = \
            plan.build_time_s * 1e6
    row["sparse_bitwise_equal"] = bool(np.array_equal(
        np.asarray(paths["us_step_matmul_sparse"](x3)),
        np.asarray(paths["us_step_matmul_subblocked"](x3))))
    return row


def _case_wide(shape: str, r: int, t: int, xw) -> dict:
    """One wide-grid case: the column-tiled substrate that auto
    resolution picks when full width cannot fit the (reduced) VMEM
    budget.  Per-step reads follow the three-factor product
    (1 + 2h/strip_m)(1 + 2w_block/w_tile)·H·W·D vs the whole-width
    whole-strip pin's analytic 3x."""
    spec = StencilSpec(shape, 2, r)
    w = make_weights(spec, seed=r)
    halo = r * t
    old_budget = os.environ.get("REPRO_VMEM_BUDGET")
    os.environ["REPRO_VMEM_BUDGET"] = str(WIDE_BUDGET)
    try:
        geom = resolve_substrate_geom(N_WIDE, halo, DTYPE_BYTES)
        assert geom.w_tile > 0, \
            f"wide case failed to column-tile: {geom} (budget {WIDE_BUDGET})"
        bands = build_bands(w.astype(np.float32),
                            common.choose_tile(N_WIDE[-1])).shape

        row = {
            "case": f"{spec.name}-t{t}-wide", "shape": shape, "r": r, "t": t,
            "boundary": "periodic",
            "grid": list(N_WIDE), "vmem_budget": WIDE_BUDGET,
            "strip_m": geom.strip_m, "h_block": geom.h_block,
            "w_tile": geom.w_tile, "w_block": geom.w_block,
            "read_amp_wholestrip": substrate_read_amp(geom.strip_m,
                                                      geom.strip_m),
            "read_amp_coltiled": geom.read_amp,
            "read_bytes_step_direct_wholestrip":
                common.hbm_read_bytes_per_step(
                    N_WIDE, geom.strip_m, DTYPE_BYTES,
                    h_block=geom.strip_m) / t,
            "read_bytes_step_direct_coltiled":
                common.hbm_read_bytes_per_step(
                    N_WIDE, geom.strip_m, DTYPE_BYTES,
                    h_block=geom.h_block, w_tile=geom.w_tile,
                    w_block=geom.w_block) / t,
            "read_bytes_step_matmul_coltiled":
                common.hbm_read_bytes_per_step(
                    N_WIDE, geom.strip_m, DTYPE_BYTES, bands_shape=bands,
                    h_block=geom.h_block, w_tile=geom.w_tile,
                    w_block=geom.w_block) / t,
        }

        pins = dict(tile_m=geom.strip_m, interpret=True)
        col = dict(h_block=geom.h_block, w_tile=geom.w_tile,
                   w_block=geom.w_block)
        paths = {
            "us_step_direct_coltiled": stencil_plan(
                w, N_WIDE, xw.dtype, t, backend="fused_direct",
                **col, **pins),
            "us_step_matmul_coltiled": stencil_plan(
                w, N_WIDE, xw.dtype, t, backend="fused_matmul_reuse",
                **col, **pins),
        }
        iters = 1 if os.environ.get("BENCH_QUICK") else 3
        for key, plan in paths.items():
            row[key] = time_us(plan, xw, iters=iters) / t
            row[key.replace("us_step_", "plan_build_us_")] = \
                plan.build_time_s * 1e6
        return row
    finally:
        if old_budget is None:
            os.environ.pop("REPRO_VMEM_BUDGET", None)
        else:
            os.environ["REPRO_VMEM_BUDGET"] = old_budget


#: Boundary-mode sweep (DESIGN.md §15): the sub-blocked VPU and
#: intermediate-reuse MXU plans under each non-periodic mode (plus the
#: periodic pin and a mixed per-axis spec), oracle-checked per row.
CASES_BOUNDARY = ["periodic", "zero", "reflect", "replicate",
                  ("reflect", "periodic")]
QUICK_CASES_BOUNDARY = ["periodic", "reflect"]
#: Overlap-vs-serialized pair geometry (2-device subprocess).
OVERLAP_GRID, OVERLAP_T = (256, 256), 4


def _case_boundary(mode, x) -> dict:
    """Time the sub-blocked plans under one boundary spec; per-step
    boundary fills are VPU row-selects, so non-periodic rows should sit
    within noise of the periodic pin -- the column makes that claim
    checkable across PRs."""
    spec = StencilSpec("box", 2, 1)
    w = make_weights(spec, seed=1)
    t = 2
    modes = resolve_boundary(mode, 2)
    row = {"case": f"boundary-{boundary_label(modes)}", "shape": "box",
           "r": 1, "t": t, "boundary": boundary_label(modes)}
    paths = {
        "us_step_direct_subblocked": stencil_plan(
            w, x.shape, x.dtype, t, backend="fused_direct",
            tile_m=TILE, boundary=mode, interpret=True),
        "us_step_matmul_subblocked": stencil_plan(
            w, x.shape, x.dtype, t, backend="fused_matmul_reuse",
            tile_m=TILE, tile_n=TILE, boundary=mode, interpret=True),
    }
    iters = 2 if os.environ.get("BENCH_QUICK") else 5
    for key, plan in paths.items():
        row[key] = time_us(plan, x, iters=iters) / t
        row[key.replace("us_step_", "plan_build_us_")] = \
            plan.build_time_s * 1e6
    ref = np.asarray(apply_stencil_steps(x, jnp.asarray(w, x.dtype), t,
                                         modes))
    row["oracle_max_err"] = max(
        float(np.max(np.abs(np.asarray(p(x)) - ref)))
        for p in paths.values())
    return row


def _halo_overlap_row(devices) -> dict:
    """Distributed overlap-vs-serialized timing pair on ``devices``.

    The serialized-exchange foil executes each step as two dispatches
    with a host sync between them -- the exchange must COMPLETE before
    the compute launches, which is exactly what a runtime without
    overlap pays.  The overlap stepper is one dispatch for all t steps
    with the interior scheduled against the in-flight ppermute pair.
    """
    import time

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.stencil.distributed import (
        _extend, apply_stencil_valid, make_distributed_stepper,
        overlap_stats, reset_overlap_stats)

    (h, wdt), t, r = OVERLAP_GRID, OVERLAP_T, 1
    mesh = Mesh(np.array(devices), ("i",))
    dims = ("i", None)
    w = make_weights(StencilSpec("box", 2, r), seed=0)
    x = np.random.default_rng(0).normal(size=(h, wdt)).astype(np.float32)
    spec = P("i", None)
    xd = jax.device_put(jnp.asarray(x), NamedSharding(mesh, spec))
    wj = jnp.asarray(w)
    ext = jax.jit(jax.shard_map(lambda a: _extend(a, r, dims), mesh=mesh,
                                in_specs=(spec,), out_specs=spec,
                                check_vma=False))
    comp = jax.jit(jax.shard_map(lambda a: apply_stencil_valid(a, wj),
                                 mesh=mesh, in_specs=(spec,),
                                 out_specs=spec, check_vma=False))

    def serialized(a):
        for _ in range(t):
            e = ext(a)
            e.block_until_ready()      # exchange completes first
            a = comp(e)
        return a.block_until_ready()

    reset_overlap_stats()
    overlap = jax.jit(make_distributed_stepper(mesh, dims, w, t=t,
                                               mode="overlap"))
    y_ser = serialized(xd)                       # warmup + reference
    y_ov = overlap(xd).block_until_ready()       # traces counters
    stats = overlap_stats()

    def best_us(fn, iters=5):
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            fn()
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    us_ser = best_us(lambda: serialized(xd)) / t
    us_ov = best_us(lambda: overlap(xd).block_until_ready()) / t
    return {
        "devices": len(devices), "platform": devices[0].platform,
        "grid": [h, wdt], "t": t, "r": r, "us_step_serialized": us_ser,
        "us_step_overlap": us_ov,
        "overlap_faster": us_ov < us_ser,
        "bitwise_equal": bool(jnp.all(y_ser == y_ov)),
        "interleave_counters": stats,
    }


def _case_halo_overlap() -> dict:
    """The overlap-vs-serialized pair (``_halo_overlap_row``).  On a TPU
    it runs in this process on its devices -- the process holds the
    chips, so a child could not reach them -- and is skipped, explicitly,
    with fewer than 2.  On the CPU it runs in a subprocess with 2 forced
    host devices, because the host-device count pins at first jax init
    (the benchmark process itself must stay single-device)."""
    import jax

    if jax.default_backend() == "tpu":
        devices = jax.devices()
        if len(devices) < 2:
            return {"case": "halo-overlap", "skipped": "needs >= 2 devices"}
        return {"case": "halo-overlap", **_halo_overlap_row(devices)}
    code = ("import json, jax\n"
            "from benchmarks.traffic import _halo_overlap_row\n"
            "print(json.dumps(_halo_overlap_row(jax.devices())))")
    root = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560)
    if r.returncode != 0:
        print(f"traffic: halo_overlap subprocess failed:\n{r.stderr}",
              file=sys.stderr)
        return {"case": "halo-overlap", "error": r.stderr[-2000:]}
    row = json.loads(r.stdout.strip().splitlines()[-1])
    row["case"] = "halo-overlap"
    return row


def _budgeted(fn, label: str, *args) -> dict:
    """Run one case under the per-case wall-clock budget; a blown budget
    records a ``timed_out`` row instead of wedging the whole sweep."""
    try:
        with case_budget():
            return fn(*args)
    except CaseTimeout as e:
        print(f"traffic: case {label} timed out ({e}); continuing",
              file=sys.stderr)
        return {"case": label, "timed_out": True, "error": str(e)}


def run() -> list[str]:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(N, N)).astype(np.float32))
    x3 = jnp.asarray(rng.normal(size=N3).astype(np.float32))
    quick = bool(os.environ.get("BENCH_QUICK"))
    radii = QUICK_RADII if quick else RADII
    depths = QUICK_DEPTHS if quick else DEPTHS
    rows = [_budgeted(_case, f"{shape}2d-r{r}-t{t}", shape, r, t, x)
            for shape in SHAPES for r in radii for t in depths]
    cases3d = QUICK_CASES_3D if quick else CASES_3D
    rows3d = [_budgeted(_case3d, f"{shape}3d-r{r}-t{t}", shape, r, t, x3)
              for shape, r, t in cases3d]
    xw = jnp.asarray(rng.normal(size=N_WIDE).astype(np.float32))
    cases_wide = QUICK_CASES_WIDE if quick else CASES_WIDE
    rows_wide = [_budgeted(_case_wide, f"{shape}2d-r{r}-t{t}-wide",
                           shape, r, t, xw)
                 for shape, r, t in cases_wide]
    cases_boundary = QUICK_CASES_BOUNDARY if quick else CASES_BOUNDARY
    rows_boundary = [_budgeted(_case_boundary, f"boundary-{mode}", mode, x)
                     for mode in cases_boundary]
    row_overlap = _budgeted(_case_halo_overlap, "halo-overlap")

    with open(JSON_PATH_QUICK if quick else JSON_PATH, "w") as f:
        json.dump({"grid": N, "tile": TILE, "dtype_bytes": DTYPE_BYTES,
                   "quick": quick, "radii": list(radii),
                   "depths": list(depths),
                   "grid_3d": list(N3),
                   "slab_3d": [SLAB3, STRIP3, TILE3],
                   "grid_wide": list(N_WIDE),
                   "vmem_budget_wide": WIDE_BUDGET,
                   "timing": "interpret-mode CPU (relative only)",
                   "cases": rows, "cases_3d": rows3d,
                   "cases_wide": rows_wide,
                   "cases_boundary": rows_boundary,
                   "halo_overlap": row_overlap,
                   # Guard-layer record of the sweep: empty on a clean
                   # run (asserted by scripts/verify.sh) -- any event
                   # here means a kernel failed and degraded mid-bench.
                   "guard_events": guard_events.snapshot(),
                   "plan_stats": plan_cache_stats()}, f, indent=1)
    rows = [c for c in rows if not c.get("timed_out")]
    rows3d = [c for c in rows3d if not c.get("timed_out")]
    rows_wide = [c for c in rows_wide if not c.get("timed_out")]
    rows_boundary = [c for c in rows_boundary if not c.get("timed_out")]

    out = ["traffic.case,loads_whole/sub,read_amp_whole,read_amp_sub,"
           "rdMB_step_mm_whole,rdMB_step_mm_sub,us_dir_sub,us_mm_sub,"
           "us_mm_sparse,kept_S,sparse_bitwise"]
    for c in rows:
        out.append(
            f"traffic.{c['case']},{c['loads_per_tile_wholestrip']}/"
            f"{c['loads_per_tile_subblocked']},"
            f"{c['read_amp_wholestrip']:.2f}x,"
            f"{c['read_amp_subblocked']:.2f}x,"
            f"{c['read_bytes_step_matmul_wholestrip']/2**20:.3f},"
            f"{c['read_bytes_step_matmul_subblocked']/2**20:.3f},"
            f"{c['us_step_direct_subblocked']:.0f},"
            f"{c['us_step_matmul_subblocked']:.0f},"
            f"{c['us_step_matmul_sparse']:.0f},"
            f"{c['kept_row_fraction']:.4f},{c['sparse_bitwise_equal']}")

    out.append("traffic3d.case,read_amp_whole,read_amp_sub,"
               "rdMB_step_mm_whole,rdMB_step_mm_sub,us_dir_sub,"
               "us_mm_sub,us_mm_sparse,kept_S,sparse_bitwise")
    for c in rows3d:
        out.append(
            f"traffic3d.{c['case']},{c['read_amp_wholestrip']:.2f}x,"
            f"{c['read_amp_subblocked']:.2f}x,"
            f"{c['read_bytes_step_matmul_wholestrip']/2**20:.3f},"
            f"{c['read_bytes_step_matmul_subblocked']/2**20:.3f},"
            f"{c['us_step_direct_subblocked']:.0f},"
            f"{c['us_step_matmul_subblocked']:.0f},"
            f"{c['us_step_matmul_sparse']:.0f},"
            f"{c['kept_row_fraction']:.4f},{c['sparse_bitwise_equal']}")

    out.append("trafficwide.case,w_tile/w_block,read_amp_whole,"
               "read_amp_coltiled,rdMB_step_dir_whole,rdMB_step_dir_col,"
               "us_dir_col,us_mm_col")
    for c in rows_wide:
        out.append(
            f"trafficwide.{c['case']},{c['w_tile']}/{c['w_block']},"
            f"{c['read_amp_wholestrip']:.2f}x,{c['read_amp_coltiled']:.2f}x,"
            f"{c['read_bytes_step_direct_wholestrip']/2**20:.3f},"
            f"{c['read_bytes_step_direct_coltiled']/2**20:.3f},"
            f"{c['us_step_direct_coltiled']:.0f},"
            f"{c['us_step_matmul_coltiled']:.0f}")

    out.append("trafficboundary.case,boundary,us_dir_sub,us_mm_sub,"
               "oracle_max_err")
    for c in rows_boundary:
        out.append(
            f"trafficboundary.{c['case']},{c['boundary']},"
            f"{c['us_step_direct_subblocked']:.0f},"
            f"{c['us_step_matmul_subblocked']:.0f},"
            f"{c['oracle_max_err']:.2e}")
    if "us_step_overlap" in row_overlap:
        c = row_overlap
        out.append("trafficoverlap.case,devices,t,us_step_serialized,"
                   "us_step_overlap,overlap_faster,bitwise,"
                   "interior_before_recv")
        out.append(
            f"trafficoverlap.halo-overlap,{c['devices']},{c['t']},"
            f"{c['us_step_serialized']:.0f},{c['us_step_overlap']:.0f},"
            f"{c['overlap_faster']},{c['bitwise_equal']},"
            f"{c['interleave_counters']['interior_before_recv_consumed']}")
    return out


if __name__ == "__main__":
    print("\n".join(run()))
