"""From a profiler trace to the device numbers the benchmark reports.

The window runs under ``jax.profiler``; its ``.xplane.pb`` is read with
``jax.profiler.ProfileData`` and reduced to plain event lists:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
* host spans: the benchmark's own ``bench.*`` annotations.

From those, per chip and then averaged over the chips used:

* busy time: the union of op intervals inside the ``bench.window`` span;
* kernel time: ops that are Pallas kernels: custom calls to
  ``tpu_custom_call``, which the trace names after the kernel's jitted
  function (``run.1``), so they are told by their stats;
* collective time: collective-permute, all-reduce, all-gather and the
  like (the busiest chip's total is kept as well);
* idle gaps: the holes in the busy union, each laid at the door of the
  innermost host span open at its midpoint.

``bench/tests/data`` keeps small traces recorded on the chip, on which
the test of this module fixes these numbers.
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
from typing import Dict, List, Set, Tuple

import numpy as np

#: An event: (name, start_ns, duration_ns).
Event = Tuple[str, float, float]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(collective-permute|all-reduce|all-gather|"
                        r"reduce-scatter|all-to-all|send|recv)")
KERNEL_TARGET = "tpu_custom_call"
#: The opcode in an op's HLO text: ``%run.1 = f32[...]{...} custom-call(``.
OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")


@dataclasses.dataclass
class Trace:
    """The parts of one trace the reduction reads."""

    devices: Dict[int, List[Event]]        # device index -> ops
    host: List[Event]                       # bench.* spans, any thread
    kernels: Set[str] = dataclasses.field(default_factory=set)


def op_parts(name: str) -> Tuple[str, str]:
    """``(instruction, opcode)`` of a device op.  The TPU trace names an
    op by its HLO text (``%run.1 = f32[..] custom-call(...), ...``); a
    bare name (``fusion.7``) gives its opcode without the number."""
    if not name.startswith("%"):
        return name, re.sub(r"[.:]\d+$", "", name)
    instr, _, rest = name[1:].partition(" = ")
    m = OPCODE.search(" " + rest)
    return instr, m.group(1) if m else instr


def _is_kernel(event) -> bool:
    """Whether a device op is a Pallas kernel: a custom call to
    ``tpu_custom_call``, by its HLO text or its stats."""
    if KERNEL_TARGET in event.name:
        return True
    try:
        return any(KERNEL_TARGET in str(v) for _, v in event.stats)
    except (TypeError, ValueError):
        return False


def read_xplane(path: str) -> Trace:
    """Device ops and benchmark host spans of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[int, List[Event]] = {}
    host: List[Event] = []
    seen: Dict[str, bool] = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                ops = devices[int(m.group(1))] = []
                for e in line.events:
                    if e.name not in seen:
                        seen[e.name] = _is_kernel(e)
                    ops.append((e.name, float(e.start_ns),
                                float(e.duration_ns)))
            elif not m:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    return Trace(devices=devices, host=host,
                 kernels={n for n, k in seen.items() if k})


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``[start, end)`` rows into disjoint sorted intervals."""
    if len(intervals) == 0:
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(iv) - 1)
    return np.stack([starts, ends[last]], axis=1)


@dataclasses.dataclass
class TraceSummary:
    """Device numbers of one traced window, per chip averaged over the
    ``chips`` used (collective time also for the busiest chip)."""

    chips: int
    window_s: float
    busy_s: float
    kernel_s: float
    collective_s: float
    collective_s_max: float
    ops_s: Dict[str, float]
    idle_s: Dict[str, float]

    def idle_share(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:10]]
        return {"device_ops": top(self.ops_s), "idle_gaps": top(self.idle_s)}


def _op_key(name: str) -> str:
    """A short name for one kind of op: the instruction without its
    instance number, and its opcode where that differs (``run.1`` a
    custom call -> ``run custom-call``; ``fusion.12`` -> ``fusion``)."""
    instr, opcode = op_parts(name)
    base = re.sub(r"[.:]\d+$", "", instr)
    return base if base == opcode else f"{base} {opcode}"


def reduce_trace(trace: Trace, chips: int) -> TraceSummary:
    """Reduce the window of ``trace`` on devices ``0..chips-1``."""
    windows = [e for e in trace.host if e[0] == WINDOW_SPAN]
    if not windows:
        raise ValueError("trace holds no bench.window span")
    _, w0, wd = windows[0]
    w1 = w0 + wd
    spans = sorted((e for e in trace.host if e[0] != WINDOW_SPAN
                    and e[0].startswith(HOST_SPAN_PREFIX)
                    and e[1] < w1 and e[1] + e[2] > w0),
                   key=lambda e: e[1])
    starts = [e[1] for e in spans]
    busy = kernel = coll = 0.0
    coll_max = 0.0
    ops: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    used = sorted(trace.devices)[:chips]
    if len(used) < chips:
        raise ValueError(f"trace holds {len(used)} device planes, "
                         f"needs {chips}")
    for dev in used:
        evs = [(n, max(s, w0), min(s + d, w1)) for n, s, d in
               trace.devices[dev] if s < w1 and s + d > w0]
        c = 0.0
        for name, s, e in evs:
            dur = e - s
            ops[_op_key(name)] += dur / chips
            if name in trace.kernels or KERNEL_TARGET in name:
                kernel += dur / chips
            if COLLECTIVE.match(op_parts(name)[1]):
                c += dur
        coll += c / chips
        coll_max = max(coll_max, c)
        iv = _union(np.array([(s, e) for _, s, e in evs],
                             dtype=np.float64).reshape(-1, 2))
        busy += float((iv[:, 1] - iv[:, 0]).sum()) / chips
        edges = np.concatenate([[w0], iv.ravel(), [w1]]).reshape(-1, 2)
        for g0, g1 in edges:
            if g1 > g0:
                idle[_host_at(spans, starts, 0.5 * (g0 + g1))] += float(
                    g1 - g0) / chips
    ns = 1e-9
    return TraceSummary(
        chips=chips, window_s=wd * ns, busy_s=busy * ns,
        kernel_s=kernel * ns, collective_s=coll * ns,
        collective_s_max=coll_max * ns,
        ops_s={k: v * ns for k, v in ops.items()},
        idle_s={k: v * ns for k, v in idle.items()})


def _host_at(spans: List[Event], starts: List[float], t: float) -> str:
    """The benchmark span open at ``t``: the latest started of the few
    before it that still runs (the benchmark's spans do not nest inside
    the window), else the window itself."""
    i = bisect.bisect_right(starts, t) - 1
    for n, s, d in spans[max(i - 8, 0):i + 1][::-1]:
        if s + d >= t:
            return n
    return WINDOW_SPAN


class Tracer:
    """The profiler around one window: ``stop()`` returns its summary
    and deletes the trace files."""

    def __init__(self, chips: int):
        import jax
        self.chips = chips
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(self.dir)

    def stop(self) -> TraceSummary:
        import jax
        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            self.trace = read_xplane(path)
            return reduce_trace(self.trace, self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
