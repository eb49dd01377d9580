"""The two timed loops: time stepping one grid, and serving an ensemble.

Each driver sets up its cell (weights, plans, data made on the device
from the seed, every shape warmed up), measures for ``seconds``, closes
the window, reads the device memory peak, and only then runs the
oracle over what the window produced.  It fills a :class:`Run`, from
which ``run.py`` takes the end-to-end metrics and the per-layer readers
take theirs.

Every call into the program sits in a host span
(``jax.profiler.TraceAnnotation``) named ``bench.<what>``, so the
trace's idle gaps can be laid at the door of what the host was doing.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import arrivals, oracle


@dataclasses.dataclass
class Run:
    """What one run did, for the metrics and the checks."""

    cell: object
    seed: int
    seconds: float
    trace: bool
    peaks: dict
    chips: int
    t: int = 0
    nnz: int = 0
    points: int = 0               # grid points one call advances
    itemsize: int = 0
    plans: List[object] = dataclasses.field(default_factory=list)
    build_s: List[float] = dataclasses.field(default_factory=list)
    first_call_s: List[float] = dataclasses.field(default_factory=list)
    calls: int = 0                # plan calls (or requests) in the window
    window_s: float = 0.0         # host clock, window start to close
    setup_s: float = 0.0
    e2e: Dict[str, float] = dataclasses.field(default_factory=dict)
    serve: Optional[dict] = None
    device_trace: Optional[object] = None
    memory_peak_bytes: int = 0
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    rel_err: Optional[float] = None
    control: bool = False         # also read the control (bench/calibrate.py)
    notes: Dict[str, object] = dataclasses.field(default_factory=dict)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def weights_of(config: dict) -> np.ndarray:
    """The configuration's stencil coefficients.  They are fixed by the
    configuration and not by the run's seed: the engine compiles them
    into its kernels, so coefficients drawn per seed would compile anew
    in every run."""
    wcfg = config["weights"]
    return oracle.make_weights(config["shape"], config["dim"],
                               config["radius"], seed=wcfg["seed"],
                               normalize=wcfg["normalize"],
                               dtype=np.dtype(config["dtype"]))


def boundary_modes(config: dict) -> tuple:
    b = config["boundary"]
    return (b,) * config["dim"] if isinstance(b, str) else tuple(b)


def grid_maker(shape, dtype, sharding=None):
    """A jitted ``seed -> N(0, 1) grid`` made on the device, in one call."""
    import jax

    def make(key):
        return jax.random.normal(key, shape, dtype)

    return jax.jit(make, out_shardings=sharding)


def prng_key(seed: int, stream: int):
    """A JAX key for any whole-number seed (JAX keys take 32 bits)."""
    import jax
    word = int(arrivals.seed_rng(seed, stream).integers(0, 2**31 - 1))
    return jax.random.key(word)


def _mesh(config: dict, devices):
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mcfg = config["mesh"]
    mesh = Mesh(np.array(devices).reshape(mcfg["shape"]),
                tuple(mcfg["axes"]))
    spec = tuple(mcfg["shard_spec"])
    return mesh, spec, NamedSharding(mesh, P(*spec))


def _warm(plan, x, run: Run):
    """First call (compile or cache load) and one steady call: their
    difference is the plan's first-call cost."""
    t0 = time.perf_counter()
    with span("bench.plan_call"):
        y = plan(x)
    y.block_until_ready()
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    with span("bench.plan_call"):
        y2 = plan(y)
    y2.block_until_ready()
    run.first_call_s.append(first - (time.perf_counter() - t0))
    del y
    return y2


def calls_ahead(mix: dict, grid_bytes: int, device) -> int:
    """How many calls the step window keeps queued beyond the one it
    waits for: as many outputs as fit in the mix's ``ahead_memory_share``
    of the chip's memory beside the two grids the loop holds anyway, at
    least 1 and at most ``ahead_max_calls``.  A device that reports no
    memory limit (the CPU) gets the most."""
    most = int(mix["ahead_max_calls"])
    limit = (device.memory_stats() or {}).get("bytes_limit")
    if not limit:
        return most
    fit = int(mix["ahead_memory_share"] * limit) // grid_bytes - 2
    return max(1, min(most, fit))


def run_step(env, run: Run) -> Run:
    """Time stepping: the cell's one plan called back to back on its
    output.  The host keeps ``calls_ahead`` calls queued on the device
    beyond the one it waits for, so a host that stands still for a
    moment leaves the chip fed.  When the window's time is up it sends
    nothing more and waits for all it sent; every call counts, over all
    of that time."""
    import jax
    from repro.kernels import stencil_plan

    cfg, mix = run.cell.config, run.cell.traffic
    shape = tuple(mix.get("grid", cfg["grid"]))
    dtype = np.dtype(cfg["dtype"])
    run.t = int(mix.get("t", cfg["t"]))
    w = weights_of(cfg)
    run.nnz = int(np.count_nonzero(w))
    run.points = int(np.prod(shape))
    run.itemsize = dtype.itemsize
    modes = boundary_modes(cfg)
    devices = env.devices[:run.chips]
    kw = {}
    sharding = None
    if cfg.get("mesh"):
        mesh, spec, sharding = _mesh(cfg, devices)
        kw = dict(mesh=mesh, shard_spec=spec,
                  dist_mode=cfg["mesh"]["dist_mode"])
    else:
        sharding = jax.sharding.SingleDeviceSharding(devices[0])

    with span("bench.plan_build"):
        plan = stencil_plan(w, shape, dtype, run.t, hw=env.hw,
                            boundary=cfg["boundary"], **kw)
    run.plans.append(plan)
    run.build_s.append(plan.build_time_s)
    with span("bench.data_prep"):
        x0 = grid_maker(shape, dtype, sharding)(prng_key(run.seed, 0))
        x0.block_until_ready()
    x = _warm(plan, x0, run)
    del x0
    run.setup_s = time.perf_counter() - env.t_start

    ahead = calls_ahead(mix, x.nbytes // len(devices), devices[0])
    run.notes["calls_ahead"] = ahead
    compiles = env.compiles()
    tracer = env.start_trace() if run.trace else None
    queued = collections.deque()
    prev = None
    calls = 0
    with span("bench.window"):
        start = time.perf_counter()
        deadline = start + run.seconds
        while True:
            with span("bench.plan_call"):
                nxt = plan(x)
            calls += 1
            queued.append(nxt)
            if len(queued) > ahead:
                with span("bench.result_wait"):
                    queued.popleft().block_until_ready()
            prev, x = x, nxt
            if time.perf_counter() >= deadline:
                break
        with span("bench.result_wait"):
            x.block_until_ready()
        run.window_s = time.perf_counter() - start
    queued.clear()
    run.device_trace = tracer.stop() if tracer else None
    run.compiles_in_window = env.compiles() - compiles
    run.calls = run.attempted = calls
    run.e2e["gstencil_per_s"] = (calls * run.points * run.t
                                 / run.window_s / 1e9)
    run.memory_peak_bytes = env.memory_peak(devices)

    # The oracle after the window: the last call's output against the
    # oracle of its input, at the timed size, a band of rows at a time.
    err, top = oracle.banded_errors(prev, x, w, run.t, modes,
                                    cfg["oracle_band"], devices[0])
    run.rel_err = err / top if top > 0 else float("inf")
    run.notes["max_abs_err"] = err
    if run.control:
        low, _ = oracle.banded_errors(prev, x, w, run.t, modes,
                                      cfg["oracle_band"], devices[0],
                                      low=True)
        same, _ = oracle.banded_errors(prev, prev, w, run.t, modes,
                                       cfg["oracle_band"], devices[0])
        run.notes["control_rel_err"] = low / top
        run.notes["unchanged_rel_err"] = same / top
    return run


def _due_and_done(server, weights, pool, sched, start, t):
    """Submit each request when it is due; return the futures, their
    completion stamps and how late each submit was."""
    n = len(sched.offsets_s)
    futures = [None] * n
    done = [None] * n
    late = np.zeros(n)

    def stamp(i):
        def cb(_):
            done[i] = time.perf_counter()
        return cb

    for i in range(n):
        due = start + sched.offsets_s[i]
        wait = due - time.perf_counter()
        if wait > 0:
            with span("bench.idle"):
                time.sleep(wait)
        now = time.perf_counter()
        late[i] = now - due
        with span("bench.submit"):
            fut = server.submit(weights, pool[sched.members[i]], t=t)
        fut.add_done_callback(stamp(i))
        futures[i] = fut
    return futures, done, late


#: How long after the window's close a request may still come back
#: before it counts as never answered.
GRACE_S = 60.0


def run_open_loop(env, run: Run) -> Run:
    """Serving: requests from the host, each one ensemble member, sent
    through ``StencilServer()`` with its defaults on a fixed open-loop
    schedule (``bench/arrivals.py``).  Latency runs from when a request
    was due to when its future resolved."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import stencil_plan
    from repro.serve import StencilServer

    cfg, mix = run.cell.config, run.cell.traffic
    shape = tuple(mix["grid"])
    dtype = np.dtype(cfg["dtype"])
    run.t = int(mix.get("t", cfg["t"]))
    w = weights_of(cfg)
    run.nnz = int(np.count_nonzero(w))
    run.points = int(np.prod(shape))
    run.itemsize = dtype.itemsize
    modes = boundary_modes(cfg)
    device = env.devices[0]
    pool_n = int(mix["pool"])

    with span("bench.data_prep"):
        pool_dev = grid_maker((pool_n,) + shape, dtype)(
            prng_key(run.seed, 0))
        pool = [np.array(p) for p in np.asarray(pool_dev)]
    server = StencilServer(hw=env.hw)
    try:
        # Warm every bucket plan the dispatcher can build for this
        # signature: the same stencil_plan signature it asks for, so it
        # finds them in the plan cache.
        for bucket in server.buckets:
            if bucket > server.max_batch:
                continue
            with span("bench.plan_build"):
                plan = stencil_plan(w, shape, dtype, run.t, hw=env.hw,
                                    interpret=server.interpret,
                                    compute_dtype=server.compute_dtype,
                                    batch=bucket,
                                    batch_mode=server.batch_mode)
            run.plans.append(plan)
            run.build_s.append(plan.build_time_s)
            xb = jnp.zeros((bucket,) + shape, dtype)
            _warm(plan, xb, run).block_until_ready()
        with span("bench.result_wait"):
            server.submit(w, pool[0], t=run.t).result(timeout=GRACE_S)
        server.metrics.reset()
        sched = arrivals.schedule(mix, run.seed, run.seconds, pool_n)
        run.setup_s = time.perf_counter() - env.t_start

        compiles = env.compiles()
        tracer = env.start_trace() if run.trace else None
        with span("bench.window"):
            start = time.perf_counter()
            futures, done, late = _due_and_done(server, w, pool, sched,
                                                start, run.t)
            close = start + run.seconds
            outs = []
            for fut in futures:
                with span("bench.result_wait"):
                    try:
                        outs.append(fut.result(
                            timeout=max(close + GRACE_S
                                        - time.perf_counter(), 0.0)))
                    except Exception as e:  # noqa: BLE001 -- counted
                        outs.append(e)
            run.window_s = time.perf_counter() - start
        run.device_trace = tracer.stop() if tracer else None
        run.compiles_in_window = env.compiles() - compiles
    finally:
        server.shutdown()
    run.serve = server.stats()
    run.plans = [p.plan for p in server.engine_plans()] or run.plans
    run.memory_peak_bytes = env.memory_peak([device])

    n = len(futures)
    ok = [i for i in range(n) if not isinstance(outs[i], Exception)
          and done[i] is not None]
    run.attempted, run.failed = n, n - len(ok)
    run.calls = n
    lat_ms = [(done[i] - (start + sched.offsets_s[i])) * 1e3 for i in ok]
    if ok:
        # A failed request misses every latency limit; it shows in
        # ``failed``, which makes the run not correct.
        run.e2e["serve_p95_ms"] = arrivals.percentile(lat_ms, 95)
        run.e2e["serve_req_per_s"] = len(ok) / (
            max(done[i] for i in ok) - start)
        run.notes["serve_p50_ms"] = arrivals.percentile(lat_ms, 50)
    run.notes["generator_late_p95_ms"] = arrivals.percentile(
        list(late * 1e3), 95)
    run.notes["generator_late_max_ms"] = float(late.max() * 1e3)

    # The oracle of every pool member, after the window; each answer is
    # held against the oracle of the member it sent.
    def steps(g, dt):
        return oracle.apply_stencil_steps(g.astype(dt), jnp.asarray(w, dt),
                                          run.t, modes).astype(g.dtype)

    members = jnp.asarray(np.stack(pool))
    ref = np.asarray(jax.jit(jax.vmap(lambda g: steps(g, dtype)))(members))
    err = 0.0
    for i in ok:
        err = max(err, float(np.max(np.abs(outs[i]
                                            - ref[sched.members[i]]))))
    top = float(np.max(np.abs(ref)))
    run.rel_err = err / top if ok else float("inf")
    run.notes["max_abs_err"] = err
    if run.control:
        low = np.asarray(jax.jit(jax.vmap(
            lambda g: steps(g, jnp.bfloat16)))(members))
        run.notes["control_rel_err"] = float(np.max(np.abs(low - ref))) / top
        run.notes["unchanged_rel_err"] = float(
            np.max(np.abs(np.stack(pool) - ref))) / top
    return run


DRIVERS = {"step": run_step, "open_loop": run_open_loop}
