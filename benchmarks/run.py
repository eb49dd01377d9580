"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` style CSV lines; each sub-benchmark
documents its own columns in the header line it emits.

Wall-clock numbers inside the benchmarks come from ``benchmarks.timing
.time_us`` (warmup + ``block_until_ready`` per call), so they measure
steady-state execution, never import or trace+compile.  The harness-level
``bench.<mod>.total`` line is bookkeeping (how long the module took to
produce its lines), timed AFTER all modules are imported.

Set BENCH_QUICK=1 to trim the slowest sweeps (used by scripts/verify.sh).

A module's failure does not stop the sweep (it must finish and report
every module it can) but is never lost: each run writes
``BENCH_run.json`` -- the manifest of which modules succeeded and which
failed, with the error string -- and exits 1 when any module failed.
"""
from __future__ import annotations

import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

MANIFEST_PATH = "BENCH_run.json"


def main() -> int:
    from repro.core.envutil import init_compile_cache
    init_compile_cache()
    # Import everything up front: module import cost must never leak into
    # any timed region.
    from benchmarks import (fig10, fig16, halo, table2, table3, table4,
                            traffic)
    from repro.kernels import plan_cache_stats

    modules = []
    for mod in (table2, table3, table4, fig10, fig16, halo, traffic):
        name = mod.__name__.split(".")[-1]
        t0 = time.perf_counter()
        try:
            lines = mod.run()
            dt = (time.perf_counter() - t0) * 1e6
            for line in lines:
                print(line)
            print(f"bench.{name}.total,{dt:.0f},us_wall")
            modules.append({"module": name, "ok": True,
                            "wall_us": round(dt)})
        except Exception as e:
            traceback.print_exc()
            print(f"bench.{name}.FAILED,0,{e}")
            modules.append({"module": name, "ok": False,
                            "error": f"{type(e).__name__}: {e}"})

    # bookkeeping: one plan per distinct kernel signature across the whole
    # harness; hits = timed paths that reused an already-built plan
    st = plan_cache_stats()
    print(f"bench.plan_cache,{st['misses']},plans_built,"
          f"{st['hits']},cache_hits")

    with open(MANIFEST_PATH, "w") as f:
        json.dump({
            "quick": bool(os.environ.get("BENCH_QUICK")),
            "modules": modules,
            "failed": [m["module"] for m in modules if not m["ok"]],
            "succeeded": [m["module"] for m in modules if m["ok"]],
        }, f, indent=1)
    return 1 if any(not m["ok"] for m in modules) else 0


if __name__ == "__main__":
    sys.exit(main())
