"""Run one benchmark cell once on the accelerator and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run it from the root of a checkout.  The cell's entry in
``BENCHMARK.json`` names its configuration, traffic mix and metrics
(``bench/benchspec.py``).  The run makes its data on the device from
``--seed``, warms up every shape it will use (set-up), measures for
``--seconds``, then checks what the window produced against the
benchmark's own oracle.  With ``--trace 0`` the result carries the
cell's end-to-end metrics; with ``--trace 1`` the window runs under the
profiler and the result carries the per-layer metrics.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and, traced,
``breakdown``), and last ``checks``: each number the run compared, with
its limit, also printed as the last lines of standard error.  Without a
TPU, or with fewer chips than the cell asks for, it prints no result
and exits 1.  A run that is not correct exits 1 after its result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: JAX's persistent compilation cache when ``JAX_COMPILATION_CACHE_DIR``
#: is unset: a fixed path inside the checkout, so that the path (part of
#: the cache key) never moves and only a cell's first run compiles.
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
#: The ``jax.monitoring`` event JAX records around making an executable.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Env:
    """The process's accelerator, clocks and counters, as the drivers
    see them.  Tests build one on the CPU and steer it."""

    def __init__(self, devices, hw, peaks, t_start, allow_interpret=False):
        self.devices = list(devices)
        self.hw = hw
        self.peaks = peaks
        self.t_start = t_start
        self.allow_interpret = allow_interpret
        self._compiles = 0
        import jax

        def on_duration(name, _secs, **_kw):
            # Fires for every executable made: compiled or loaded from
            # the persistent cache.
            if name == COMPILE_EVENT:
                self._compiles += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def compiles(self) -> int:
        return self._compiles

    def start_trace(self):
        from bench.trace_reduce import Tracer
        return Tracer(len(self.devices))

    @staticmethod
    def memory_peak(devices) -> int:
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in devices]
        return int(max(peaks))


def setup_jax():
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") is None:
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def program_checks(run, env) -> dict:
    """The program's own health in this run, each as ``{value, limit}``:
    plans compiled (not interpreted), no plan answering from the
    reference backend, no guard event or fallback, nothing compiled in
    the window."""
    from repro.core import events
    from repro.kernels import plan_cache_stats

    stats = plan_cache_stats()
    guard = [e for e in events.events() if e["kind"].startswith("guard")]
    interp = sum(bool(p.interpret) for p in run.plans)
    return {
        "interpret_plans": {"value": 0 if env.allow_interpret else interp,
                            "limit": 0},
        "reference_plans": {"value": sum(p.backend == "reference"
                                         for p in run.plans), "limit": 0},
        "guard_events": {"value": len(guard), "limit": 0},
        "fallbacks": {"value": stats["fallbacks"] + stats["build_failures"]
                      + stats["exec_failures"], "limit": 0},
        "compiles_in_window": {"value": run.compiles_in_window, "limit": 0},
        "failed_requests": {"value": run.failed, "limit": 0},
    }


def run_cell(cell, seed: int, seconds: float, trace: bool, env,
             control: bool = False) -> dict:
    """Drive one cell and build its result line (a dict).  ``control``
    also reads the control's numbers into the notes (``calibrate.py``);
    the benchmark's own runs do not."""
    from bench import benchspec
    from bench.cell import DRIVERS, Run

    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              peaks=env.peaks, chips=cell.chips, control=control)
    DRIVERS[cell.traffic["driver"]](env, run)
    run.e2e["setup_s"] = run.setup_s

    rel = run.rel_err if run.rel_err is not None and math.isfinite(
        run.rel_err) else None
    checks = {"rel_err": {"value": rel, "limit": cell.limits["rel_err"]}}
    checks.update(program_checks(run, env))
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = benchspec.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in run.e2e:        # absent only when nothing ran
                metrics[m["name"]] = {"value": run.e2e[m["name"]],
                                      "unit": m["unit"]}
    dev = env.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(env.devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if run.device_trace is not None:
        device["busy_s"] = run.device_trace.busy_s
        device["window_s"] = run.device_trace.window_s
        out["breakdown"] = run.device_trace.breakdown()
    out["notes"] = dict(run.notes, setup_s=run.setup_s,
                        window_s=run.window_s, calls=run.calls)
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, ROOT)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from bench import benchspec, roofline
    cell = benchspec.resolve(args.workload)
    src = os.path.join(ROOT, "src")
    try:
        import repro  # the system under test
    except ImportError as e:
        print(f"bench: cannot import the program from {src}: {e}",
              file=sys.stderr)
        return 1
    where = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(src + os.sep) for p in where):
        print(f"bench: the program imported from {where}, not from {src}",
              file=sys.stderr)
        return 1
    jax = setup_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devices[0].platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    from repro.core.perfmodel import hardware_for
    env = Env(devices, hardware_for(devices[0]),
              roofline.peaks_for(devices[0].device_kind), T_START)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), env)
    for name, c in result["checks"].items():
        print(f"check {name}={c['value']} limit={c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
