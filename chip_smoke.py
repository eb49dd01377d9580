"""Chip smoke: drive the stencil engine's main paths once on a TPU.

    python chip_smoke.py                # one chip: phases a-e
    python chip_smoke.py --four-chips   # the sharded-grid path on a 2x2 host

Every phase goes through the entry points a user calls -- ``stencil_plan``
(unguarded) for time stepping, ``StencilServer(guard=False)`` for serving
-- at chip-filling sizes, with inputs drawn on the device from a seed,
and checks the answers against the XLA oracle (``stencil/reference.py``)
run on the same chip.  A phase fails when a plan resolved interpret mode,
ran another backend than the one requested or selected, degraded, or
missed the oracle by more than ``F32_TOL``.  Each phase prints one line;
its ``setup_s`` is wall time including compiles, i.e. set-up, not a
measurement.  The last line of a passing run is the JSON device record.

There is no CPU path: without a TPU the script exits 1 and prints no
result.  Run it alone in one process -- it holds the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

#: Largest |plan - oracle| admitted in f32: the tolerance the repo's own
#: oracle tests use for fused 2D/3D steps of N(0, 1) grids.
F32_TOL = 2e-4

BOX2 = ("box", 2, 1)
STAR2 = ("star", 2, 1)
STAR3 = ("star", 3, 1)


class SmokeFailure(AssertionError):
    """A smoke check failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _weights(spec):
    from repro.stencil import StencilSpec, make_weights
    return make_weights(StencilSpec(*spec), seed=0)


def _grid(shape, seed: int = 0, batch: int = None):
    """An N(0, 1) float32 grid drawn on the default device."""
    import jax
    import jax.numpy as jnp
    full = tuple(shape) if batch is None else (batch,) + tuple(shape)
    return jax.random.normal(jax.random.key(seed), full, jnp.float32)


def _oracle(x, w, steps: int, boundary=None):
    import jax.numpy as jnp
    from repro.stencil.reference import apply_stencil_steps
    return apply_stencil_steps(x, jnp.asarray(w, x.dtype), steps,
                               boundary if boundary is not None
                               else "periodic")


def _oracle_by_rows(x, w, steps: int, band: int):
    """The periodic oracle computed ``band`` rows at a time, each band
    extended by the ``steps * r`` rows it depends on: a grid whose
    whole-grid oracle (about 9 grid-sized temporaries) would not fit one
    chip's memory is still checked on one chip."""
    import jax.numpy as jnp
    n, h = x.shape[0], steps * ((w.shape[0] - 1) // 2)
    parts = []
    for lo in range(0, n, band):
        rows = jnp.arange(lo - h, lo + band + h) % n
        parts.append(_oracle(jnp.take(x, rows, axis=0), w, steps)[h:h + band])
    return jnp.concatenate(parts, axis=0)


def _max_err(a, b) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def _geometry(plan) -> str:
    """The substrate clause of the plan's decision reason."""
    return next((part.strip() for part in plan.decision.reason.split("|")
                 if "substrate" in part), "")


def _column_shifts(plan) -> str:
    """The column-shift form (``roll`` or ``slice``) the plan's VPU tap
    sums trace, as its decision record states it; ``-`` where the plan
    runs no VPU tap sum, or its record is of another unit's pick."""
    from repro.kernels.registry import candidate_units
    if candidate_units().get(plan.backend) != "vector":
        return "-"
    return next((part.split("=")[1].strip() for part in
                 plan.decision.reason.split("|")
                 if "column shifts=" in part), "-")


def _check_plan(plan, requested, interpret) -> None:
    """Plans must resolve the expected interpret mode (compiled on the
    chip) and run the backend asked for, or the one auto selected."""
    want = False if interpret is None else interpret
    _check(plan.interpret is want,
           f"plan resolved interpret={plan.interpret}, expected {want}")
    expected = requested if requested is not None else plan.decision.backend
    _check(plan.backend == expected,
           f"plan runs {plan.backend!r}, expected {expected!r}")
    _check(plan.backend != "reference", "plan answers from the reference")


def _step_plans(name, spec, shape, t, n_steps, backends, interpret=None,
                boundary=None, hw=None, oracle_band=None):
    """Time-step ``shape`` with each backend (None = auto) for
    ``n_steps`` plan invocations and compare with the oracle's
    ``t * n_steps`` steps (``oracle_band`` rows at a time when set).
    One result dict per backend."""
    from repro.core import perfmodel as pm
    from repro.kernels import stencil_plan

    w = _weights(spec)
    x = _grid(shape)
    t0 = time.perf_counter()
    if oracle_band:
        ref = _oracle_by_rows(x, w, t * n_steps, oracle_band)
    else:
        ref = _oracle(x, w, t * n_steps, boundary)
    ref.block_until_ready()
    ref_s = time.perf_counter() - t0
    rows = []
    for backend in backends:
        t0 = time.perf_counter()
        plan = stencil_plan(w, shape, x.dtype, t, backend=backend,
                            interpret=interpret, boundary=boundary,
                            hw=hw or pm.TPU_V5E_BF16)
        _check_plan(plan, backend, interpret)
        y = plan.run(x, n_steps=n_steps).block_until_ready()
        err = _max_err(y, ref)
        rows.append({
            "phase": name, "backend": plan.backend,
            "column_shifts": _column_shifts(plan),
            "requested": backend or "auto", "interpret": plan.interpret,
            "grid": list(shape), "t": t, "steps": t * n_steps,
            "boundary": "periodic" if boundary is None else boundary,
            "geometry": _geometry(plan), "max_err": err, "tol": F32_TOL,
            "setup_s": time.perf_counter() - t0, "oracle_setup_s": ref_s})
        _check(err <= F32_TOL, f"{name} {plan.backend}: max err {err} > "
               f"{F32_TOL}")
        del y
    return rows


def phase_2d(n: int = 10240, t: int = 4, n_steps: int = 3, interpret=None,
             boundary=None, hw=None, name: str = "b.2d"):
    """Box-2D1R f32 n x n: the auto plan, VPU ``fused_direct`` and MXU
    ``fused_matmul_reuse``, each ``n_steps`` invocations of depth t."""
    return _step_plans(name, BOX2, (n, n), t, n_steps,
                       (None, "fused_direct", "fused_matmul_reuse"),
                       interpret, boundary, hw)


def phase_substrates(n: int = 2048, t: int = 4, interpret=None, hw=None):
    """The auto ``fused_direct`` plan against the same backend with its
    ring pinned to ``h_block = strip_m`` (three full strips per strip):
    both assemble the same halo-extended strips, so they should agree
    bit for bit; the check admits ``F32_TOL`` and reports whether they
    were bitwise equal."""
    from repro.core import perfmodel as pm
    from repro.kernels import resolve_substrate_geom, stencil_plan

    w, x = _weights(BOX2), _grid((n, n), seed=1)
    t0 = time.perf_counter()
    strip_m = resolve_substrate_geom((n, n), t, 4).strip_m
    ys = {}
    for pins in ({}, dict(tile_m=strip_m, h_block=strip_m)):
        plan = stencil_plan(w, (n, n), x.dtype, t, backend="fused_direct",
                            interpret=interpret, hw=hw or pm.TPU_V5E_BF16,
                            **pins)
        _check_plan(plan, "fused_direct", interpret)
        ys[bool(pins)] = plan(x).block_until_ready()
    a, b = ys.values()
    err = _max_err(a, b)
    _check(err <= F32_TOL, f"auto vs h_block=strip_m max diff {err}")
    return [{"phase": "b.substrates", "backend": "fused_direct",
             "pinned": f"h_block={strip_m}",
             "geometry": _geometry(plan),
             "column_shifts": _column_shifts(plan),
             "interpret": plan.interpret,
             "grid": [n, n], "t": t, "max_err": err, "tol": F32_TOL,
             "bitwise": bool((a == b).all()),
             "setup_s": time.perf_counter() - t0}]


def phase_3d(n: int = 512, t: int = 2, n_steps: int = 2, interpret=None,
             hw=None):
    """Star-3D1R f32 n^3, auto plan.  The oracle runs n/8 planes at a
    time: at 512^3 its whole-grid temporaries exceed 16 GB."""
    return _step_plans("c.3d", STAR3, (n, n, n), t, n_steps, (None,),
                       interpret, None, hw, oracle_band=max(n // 8, 1))


def phase_serving(n: int = 256, requests: int = 256, window: int = 8,
                  t: int = 2, interpret=None, hw=None):
    """``StencilServer(guard=False)`` answers ``requests`` Star-2D1R f32
    n x n requests of depth t, submitted ``window`` grids at a time;
    every answer is checked against the oracle."""
    import jax
    import numpy as np
    from repro.core import perfmodel as pm
    from repro.serve import StencilServer

    w = _weights(STAR2)
    xs = _grid((n, n), seed=2, batch=requests)
    refs = jax.vmap(lambda g: _oracle(g, w, t))(xs)
    xs_host, refs_host = np.asarray(xs), np.asarray(refs)
    t0 = time.perf_counter()
    err = 0.0
    with StencilServer(guard=False, max_batch=window, interpret=interpret,
                       hw=hw or pm.TPU_V5E_BF16) as server:
        for lo in range(0, requests, window):
            futs = [server.submit(w, xs_host[i], t=t)
                    for i in range(lo, min(lo + window, requests))]
            for i, fut in enumerate(futs, start=lo):
                y = fut.result(timeout=600)
                err = max(err, float(np.max(np.abs(y - refs_host[i]))))
        stats = server.stats()
    plans = server.engine_plans()
    for plan in plans:
        _check_plan(plan, None, interpret)
    _check(stats["responded"] == requests and stats["failed"] == 0,
           f"served {stats['responded']}/{requests}, "
           f"{stats['failed']} failed")
    _check(err <= F32_TOL, f"serving max err {err} > {F32_TOL}")
    return [{"phase": "e.serving", "backend": plans[0].backend,
             "column_shifts": _column_shifts(plans[0]),
             "batch_mode": plans[0].batch_mode,
             "interpret": plans[0].interpret, "grid": [n, n], "t": t,
             "requests": requests, "responded": stats["responded"],
             "batches": stats["batches"], "geometry": _geometry(plans[0]),
             "max_err": err, "tol": F32_TOL,
             "setup_s": time.perf_counter() - t0}]


def phase_four_chips(n: int = 20480, t: int = 4, n_steps: int = 1,
                     interpret=None, devices=None, hw=None):
    """The sharded-grid path on four chips: a 2x2 mesh sharding both axes
    (``fused`` and ``stepwise``) and a 4x1 mesh sharding rows
    (``overlap``), Pallas local kernels, each compared with the oracle of
    the same grid on one chip.  Output shardings must span all four."""
    import jax
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.core import perfmodel as pm
    from repro.kernels import stencil_plan

    devices = list(devices if devices is not None else jax.devices())
    _check(len(devices) == 4, f"needs 4 devices, found {len(devices)}")
    w = _weights(BOX2)
    x = _grid((n, n), seed=3)
    ref = _oracle_by_rows(x, w, t * n_steps, band=n // 8).block_until_ready()
    rows = []
    for mesh_shape, spec, mode in (((2, 2), ("x", "y"), "fused"),
                                   ((2, 2), ("x", "y"), "stepwise"),
                                   ((4, 1), ("x", None), "overlap")):
        t0 = time.perf_counter()
        mesh = Mesh(np.array(devices).reshape(mesh_shape), ("x", "y"))
        sharding = NamedSharding(mesh, P(*spec))
        xs = jax.device_put(x, sharding)
        plan = stencil_plan(w, (n, n), x.dtype, t, mesh=mesh,
                            shard_spec=spec, dist_mode=mode,
                            backend="fused_direct", interpret=interpret,
                            hw=hw or pm.TPU_V5E_BF16)
        _check_plan(plan, "fused_direct", interpret)
        y = plan.run(xs, n_steps=n_steps).block_until_ready()
        _check(y.sharding.is_equivalent_to(sharding, 2),
               f"{mode}: output sharding {y.sharding} != {sharding}")
        owners = {s.device for s in y.addressable_shards}
        _check(owners == set(devices), f"{mode}: output on {owners}")
        err = _max_err(y, jax.device_put(ref, y.sharding))
        rows.append({"phase": "f.four_chips", "mode": mode,
                     "mesh": list(mesh_shape), "shard_spec": list(spec),
                     "backend": plan.backend,
                     "column_shifts": _column_shifts(plan),
                     "interpret": plan.interpret,
                     "grid": [n, n], "t": t, "steps": t * n_steps,
                     "local": list(plan.halo_plan["local_shape"]),
                     "max_err": err, "tol": F32_TOL,
                     "setup_s": time.perf_counter() - t0})
        _check(err <= F32_TOL, f"{mode}: max err {err} > {F32_TOL}")
        del xs, y
    return rows


def _print_row(row: dict) -> None:
    print(" ".join(f"{k}={v:.3g}" if isinstance(v, float) else f"{k}={v}"
                   for k, v in row.items()), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-grid path on four chips")
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.core import events
        from repro.core.envutil import init_compile_cache
        from repro.core.perfmodel import hardware_for
        from repro.kernels import plan_cache_stats
    except ImportError as e:
        print(f"chip_smoke: cannot import the engine ({e}); run it from "
              "the repository root", file=sys.stderr)
        return 1
    cache = init_compile_cache()

    # a. Device check: a TPU or nothing.
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    hw = hardware_for(dev)
    print(f"a.device platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)} hw={hw} compile_cache={cache}", flush=True)

    if args.four_chips:
        phases = (lambda: phase_four_chips(devices=devices, hw=hw),)
    else:
        phases = (lambda: phase_2d(hw=hw),
                  lambda: phase_substrates(hw=hw),
                  lambda: phase_3d(hw=hw),
                  lambda: phase_2d(boundary=("reflect", "periodic"), hw=hw,
                                   name="d.boundary"),
                  lambda: phase_serving(hw=hw))
    try:
        for phase in phases:
            for row in phase():
                _print_row(row)
        guard = [e for e in events.events() if e["kind"].startswith("guard")]
        _check(not guard, f"guard events recorded: {guard}")
        st = plan_cache_stats()
        for k in ("build_failures", "exec_failures", "fallbacks"):
            _check(st[k] == 0, f"plan_cache_stats {k}={st[k]}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
