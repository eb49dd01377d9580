"""A second, short traced window that reads the program's own spans.

The main traced window (``bench/cell.py``) times the plan exactly as the
untimed runs run it, and every per-layer metric that was there before
reads that window.  This window follows it in ``--trace 1`` runs, once,
when a reader asks for it (``plan.dispatch_us``), and steps the cell's
configuration again under the profiler until ``MIN_CALLS`` calls or
``MIN_SECONDS`` have passed, whichever comes first.  Short, because a
scoped kernel traces every grid step: 20 calls of ``box2d1r-f32`` (1,920
steps each) make a 13 MB trace, and one call of ``star3d1r-f32`` holds
196,608 steps.  Which plan it steps:

* an unsharded cell runs a plan built with the program's in-kernel
  scopes compiled in (``repro.core.trace``), beside the cell's own;
* a sharded cell runs its own plan: its ``repro.dist.*`` scopes are op
  metadata, compiled in always.

From that trace it reads what ``bench/trace_reduce.py`` does not keep:

* the program's ``repro.*`` host spans: ``repro.plan.call`` gives the
  mean host time of a plan call (dispatch);
* the in-kernel scope events (``repro.kernel.compute``,
  ``repro.substrate.assemble``) on the device planes' ``XLA TraceMe``
  line: the kernel's time split into compute, assembly and the rest
  (the BlockSpec pipeline's DMA waits and per-step overhead);
* each device op's scope, the innermost ``repro.*`` name of its
  ``op_name`` in the compiled program's HLO text (the TPU trace names an
  op by its HLO text without the metadata): the time of the sharded
  stepper's exchange and local apply;
* idle gaps, each laid at the innermost ``bench.*``/``repro.*`` host
  span open at its midpoint.

The profiler runs with its Python tracer off, so that the host spans
time the program and not the tracer.  Everything goes to the run's
notes under ``program_window``.  A program
without ``repro.core.trace`` gets no window and every number is absent;
a window that fails records why in the notes and reads nothing.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
import time
import traceback
from typing import Dict, List, Optional, Set

import numpy as np

from bench import trace_reduce as tr
from bench.trace_reduce import Event

MIN_CALLS = 20
MIN_SECONDS = 0.25
HOST_PREFIXES = ("bench.", "repro.")
PLAN_CALL = "repro.plan.call"
COMPUTE = "repro.kernel.compute"
ASSEMBLE = "repro.substrate.assemble"
EXCHANGE = "repro.dist.exchange"
#: The device-plane line that holds the in-kernel scope events.
SCOPES_LINE = "XLA TraceMe"
#: The ``op_name`` metadata in a device op's HLO text.
OP_NAME = re.compile(r'op_name="([^"]*)"')
#: Iterations that time the ``repro.plan.call`` span with no profiler on.
SPAN_COST_ITERS = 20000


@dataclasses.dataclass
class ProgramTrace:
    """The parts of one trace this window reads."""

    devices: Dict[int, List[Event]]        # device index -> XLA ops
    scopes: Dict[int, List[Event]]         # device index -> scope events
    host: List[Event]                      # bench.* and repro.* spans
    kernels: Set[str] = dataclasses.field(default_factory=set)


def read_xplane(path: str) -> ProgramTrace:
    """Device ops, in-kernel scope events and host spans of one
    ``.xplane.pb``.  Other device lines (a scoped kernel's per-bundle
    ``Tensor Core`` line holds hundreds of thousands of events) are not
    read."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    scopes: Dict[int, List[Event]] = collections.defaultdict(list)
    host: List[Event] = []
    kernels: Set[str] = set()
    for plane in data.planes:
        m = tr.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == tr.OPS_LINE:
                ops = devices[int(m.group(1))] = []
                for e in line.events:
                    ops.append((e.name, float(e.start_ns),
                                float(e.duration_ns)))
                    if e.name not in kernels and tr._is_kernel(e):
                        kernels.add(e.name)
            elif m and line.name == SCOPES_LINE:
                evs = [(e.name, float(e.start_ns), float(e.duration_ns))
                       for e in line.events if e.name in (COMPUTE, ASSEMBLE)]
                if evs:
                    scopes[int(m.group(1))].extend(evs)
            elif not m:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_PREFIXES))
    return ProgramTrace(devices=devices, scopes=dict(scopes), host=host,
                        kernels=kernels)


def op_scope(line: str) -> Optional[str]:
    """The innermost ``repro.*`` scope in the ``op_name`` of one
    instruction of HLO text."""
    m = OP_NAME.search(line)
    if not m:
        return None
    parts = [p for p in m.group(1).split("/") if p.startswith("repro.")]
    return parts[-1] if parts else None


def op_scopes(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> innermost ``repro.*`` scope, for every
    instruction of a compiled program's HLO text that has one."""
    out = {}
    for line in hlo_text.splitlines():
        line = line.strip()
        if line.startswith("ROOT "):
            line = line[5:]
        scope = op_scope(line) if line.startswith("%") else None
        if scope:
            out[line[1:].split(" = ", 1)[0]] = scope
    return out


def _length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def _intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two sets of disjoint sorted intervals."""
    out = []
    i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            out.append((lo, hi))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.array(out, dtype=np.float64).reshape(-1, 2)


def _intervals(events, w0: float = -np.inf,
               w1: float = np.inf) -> np.ndarray:
    """The union of ``events`` clipped to ``[w0, w1)``."""
    iv = [(max(s, w0), min(s + d, w1)) for _, s, d in events
          if s < w1 and s + d > w0]
    return tr._union(np.array(iv, dtype=np.float64).reshape(-1, 2))


def innermost_span(spans: List[Event], starts: List[float],
                   t: float) -> str:
    """The host span open at ``t`` that started last (with properly
    nested spans, the innermost), else the window itself.  ``spans`` are
    sorted by start and ``starts`` are their starts."""
    for i in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        name, s, d = spans[i]
        if s + d >= t:
            return name
    return tr.WINDOW_SPAN


def reduce_window(trace: ProgramTrace, chips: int, calls: int,
                  scopes: Optional[Dict[str, str]] = None) -> dict:
    """The window's numbers: per chip averaged over ``chips`` (the op
    scopes' also on the busiest chip), per call over ``calls``.  Device
    numbers are absent where the trace holds no device plane.  ``scopes``
    maps instruction names to their scope (:func:`op_scopes`).

    Kernel time, its split and the op scopes count every op of the
    trace, which holds the window's calls and nothing else: in a short
    trace the host and device clocks can lie a fraction of a millisecond
    apart, and clipping to the host's window would cut ops.  Idle gaps,
    which set device time against host spans, lie inside the window."""
    windows = [e for e in trace.host if e[0] == tr.WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    _, w0, wd = windows[0]
    w1 = w0 + wd
    ms = 1e-6 / max(calls, 1)               # ns per window -> ms per call
    plan_calls = [d for n, s, d in trace.host
                  if n == PLAN_CALL and w0 <= s < w1]
    out = {"calls": calls, "window_s": wd * 1e-9,
           "plan_calls_traced": len(plan_calls)}
    if plan_calls:
        out["plan.dispatch_us"] = float(np.mean(plan_calls)) * 1e-3
    used = sorted(trace.devices)[:chips]
    if len(used) < chips:
        return out
    spans = sorted((e for e in trace.host if e[0] != tr.WINDOW_SPAN
                    and e[1] < w1 and e[1] + e[2] > w0), key=lambda e: e[1])
    starts = [e[1] for e in spans]
    kernel = compute = assemble = rest = 0.0
    scoped: Dict[str, float] = collections.defaultdict(float)
    scoped_max: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for dev in used:
        ops = trace.devices[dev]
        kern = _intervals([e for e in ops if e[0] in trace.kernels
                           or tr.KERNEL_TARGET in e[0]])
        evs = trace.scopes.get(dev, [])
        comp = _intersect(_intervals([e for e in evs if e[0] == COMPUTE]),
                          kern)
        asm = _intersect(_intervals([e for e in evs if e[0] == ASSEMBLE]),
                         kern)
        both = tr._union(np.concatenate([comp, asm]))
        kernel += _length(kern) / chips
        compute += _length(comp) / chips
        assemble += _length(asm) / chips
        rest += (_length(kern) - _length(both)) / chips
        mine: Dict[str, float] = collections.defaultdict(float)
        for name, _, d in ops:
            scope = (scopes or {}).get(tr.op_parts(name)[0])
            if scope:
                mine[scope] += d
        for scope, v in mine.items():
            scoped[scope] += v / chips
            scoped_max[scope] = max(scoped_max[scope], v)
        busy = _intervals(ops, w0, w1)
        edges = np.concatenate([[w0], busy.ravel(), [w1]]).reshape(-1, 2)
        for g0, g1 in edges:
            if g1 > g0:
                idle[innermost_span(spans, starts, 0.5 * (g0 + g1))] += \
                    float(g1 - g0) * 1e-9 / chips
    out["kernel_ms_per_call"] = kernel * ms
    if any(trace.scopes.values()):
        out["kernel.compute_ms_per_call"] = compute * ms
        out["substrate.assemble_ms_per_call"] = assemble * ms
        out["substrate.pipeline_ms_per_call"] = rest * ms
        out["split_over_kernel"] = ((compute + assemble + rest) / kernel
                                    if kernel > 0 else None)
    out["op_scope_ms_per_call"] = {k: v * ms for k, v in scoped.items()}
    if EXCHANGE in scoped_max:
        out["dist.exchange_ms_per_call"] = scoped_max[EXCHANGE] * ms
    out["idle_gaps_s"] = dict(sorted(idle.items(), key=lambda kv: -kv[1]))
    return out


def _span_cost_us() -> float:
    """Host microseconds one ``repro.plan.call`` span costs with no
    profiler running."""
    import jax
    t0 = time.perf_counter()
    for _ in range(SPAN_COST_ITERS):
        with jax.profiler.TraceAnnotation(PLAN_CALL):
            pass
    return (time.perf_counter() - t0) / SPAN_COST_ITERS * 1e6


def _window_plan(run, ktrace):
    """The plan this window steps: the cell's own when it is sharded,
    else the same plan built with the in-kernel scopes on."""
    from repro.kernels import stencil_plan
    plan = run.plans[0]
    if plan.mesh is not None:
        return plan, False
    with ktrace.kernel_scopes_on():
        scoped = stencil_plan(plan.weights, plan.grid_shape, plan.dtype,
                              plan.t, hw=plan.hw, backend=plan.backend,
                              interpret=plan.interpret,
                              boundary=plan.boundary)
    return scoped, True


def _step_window(run, plan) -> tuple:
    """Step ``plan`` on a grid made from the run's seed, under the
    profiler, as the main window does; returns ``(trace, calls,
    scopes)``, the op scopes read from the program of a sharded plan
    (a second compile, or a load from the persistent cache)."""
    import jax
    from bench.cell import calls_ahead, grid_maker, prng_key, span

    if plan.mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P
        sharding = NamedSharding(plan.mesh, P(*plan.shard_spec))
        devices = list(plan.mesh.devices.flat)
    else:
        devices = [jax.devices()[0]]
        sharding = jax.sharding.SingleDeviceSharding(devices[0])
    x = grid_maker(plan.grid_shape, plan.dtype, sharding)(
        prng_key(run.seed, 1))
    x = plan(x)                        # compile or load, outside the window
    x.block_until_ready()
    ahead = calls_ahead(run.cell.traffic, x.nbytes // len(devices),
                        devices[0])
    tmp = tempfile.mkdtemp(prefix="bench_program_")
    queued = collections.deque()
    calls = 0
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.profiler.start_trace(tmp, profiler_options=options)
        try:
            with span("bench.window"):
                start = time.perf_counter()
                while (calls < MIN_CALLS
                       and time.perf_counter() - start < MIN_SECONDS):
                    with span("bench.plan_call"):
                        x = plan(x)
                    calls += 1
                    queued.append(x)
                    if len(queued) > ahead:
                        with span("bench.result_wait"):
                            queued.popleft().block_until_ready()
                with span("bench.result_wait"):
                    x.block_until_ready()
        finally:
            jax.profiler.stop_trace()
        queued.clear()
        del x
        scopes = {}
        if plan.mesh is not None:
            arg = jax.ShapeDtypeStruct(plan.input_shape, plan.dtype,
                                       sharding=sharding)
            scopes = op_scopes(plan.fn.lower(arg).compile().as_text())
        (path,) = glob.glob(os.path.join(tmp, "plugins", "profile", "*",
                                         "*.xplane.pb"))
        return read_xplane(path), calls, scopes
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def measure(run) -> Optional[dict]:
    """The window's numbers for ``run``, measured on first use and kept
    on the run; None where there is nothing to read."""
    if not hasattr(run, "program_window"):
        run.program_window = _measure(run)
    return run.program_window


def _measure(run) -> Optional[dict]:
    if (not run.trace or run.cell.traffic["driver"] != "step"
            or not run.plans):
        return None
    try:
        from repro.core import trace as ktrace
    except ImportError:                # a program without its own spans
        return None
    t0 = time.perf_counter()
    try:
        plan, scoped = _window_plan(run, ktrace)
        trace, calls, scopes = _step_window(run, plan)
        out = reduce_window(trace, run.chips, calls, scopes)
    except Exception:  # noqa: BLE001 -- the main result must still print
        run.notes["program_window_error"] = traceback.format_exc()[-2000:]
        return None
    out["kernel_scopes"] = scoped
    main = run.device_trace
    if (scoped and main is not None and main.kernel_s > 0 and run.calls
            and out.get("kernel_ms_per_call")):
        # What compiling the scopes in costs the kernel.
        out["scope_cost"] = out["kernel_ms_per_call"] / (
            main.kernel_s / run.calls * 1e3) - 1.0
    out["plan_call_span_untraced_us"] = _span_cost_us()
    out["wall_s"] = time.perf_counter() - t0     # what the window cost
    run.notes["program_window"] = out
    return out
