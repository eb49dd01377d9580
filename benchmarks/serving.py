"""Serving benchmark: batched plan-sharing engine vs per-request dispatch.

The serving engine's claim (DESIGN.md §12) is that coalescing requests
that share a plan signature into batched launches beats dispatching each
request by itself.  This benchmark measures both sides on identical
traffic and writes BENCH_serving.json (repo root):

  * **sequential baseline** -- a closed loop that, per request, looks up
    the plan (``stencil_plan``: LRU hit after the first), executes it and
    blocks on the result.  This is the strongest honest baseline: it
    already amortizes selection/compile through the plan cache, so the
    delta vs the engine isolates *batching*, not caching.
  * **batched engine** -- the same requests through ``StencilServer``
    with a per-signature closed-loop window, so the dispatcher sees full
    queues and the coalescer emits full buckets.  Latency histograms and
    occupancy come from ``ServeMetrics``.

Both phases replay the same inputs; every engine response is compared
bitwise against the sequential plan's output for that input
(``bitwise_match`` in the JSON) -- throughput that changed the answer
would not count.

Traffic is interleaved across signatures (the coalescer's whole job);
warmup absorbs trace+compile on both sides so the measured window is
steady-state dispatch, matching the ``benchmarks/timing.time_us``
convention.  ``scripts/verify.sh`` asserts the engine beats the baseline
and that plan-cache hits grew by at least (requests - distinct
signatures) -- the plan-sharing contract.

Unlike BENCH_kernels.json, the quick sweep does NOT go to a sibling
file: P50/P99 must land in BENCH_serving.json on every verify.sh run, so
the file is always rewritten with a ``quick`` marker.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from collections import deque
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

from benchmarks.timing import CaseTimeout, case_budget
from repro.core import events as guard_events
from repro.kernels import plan_cache_stats, stencil_plan
from repro.serve import LatencyHistogram, StencilServer
from repro.stencil import StencilSpec, make_weights

GRID = (32, 32)      # small grids + t=1: dispatch overhead dominates the
                     # per-request cost, which is exactly the regime the
                     # batching engine exists for (deep-t fused launches
                     # are compute-bound and amortize on their own)
WINDOW = 128         # outstanding requests per signature (closed loop);
                     # doubles as the single batch bucket -- measured
                     # sweet spot where per-batch dispatch amortizes past
                     # the per-request future/queue overhead without the
                     # P99 blowup larger windows buy (256 -> ~50 ms tails)
N_INPUTS = 8         # distinct input grids per signature, reused round-robin
#: (shape, radius, t, dtype) per signature; quick keeps two so the
#: coalescer still has signatures to keep apart.  All f32: on the CPU
#: interpret substrate a scanned bf16 batch runs ~4x slower per element
#: than the unbatched bf16 call (XLA's bf16 emulation inside the scan
#: body), so bf16 batching is a loss here regardless of engine quality --
#: it stays covered by the bitwise equivalence sweep, not the throughput
#: claim.
SIGS_FULL = [("box", 1, 1, "float32"), ("star", 1, 1, "float32"),
             ("box", 2, 1, "float32"), ("star", 3, 1, "float32")]
SIGS_QUICK = SIGS_FULL[:2]
REQS_FULL = 8192     # requests per signature (multiples of WINDOW; sized
REQS_QUICK = 4096    # so each measured phase runs a few hundred ms --
                     # 20 ms windows measure the OS scheduler, not the
                     # engine)
JSON_PATH = os.path.join(os.path.dirname(__file__), "..",
                         "BENCH_serving.json")


@contextmanager
def _gc_quiesced():
    """Collect, then hold the cyclic GC off for one measured phase --
    applied identically to BOTH phases.  A generational collection
    landing mid-window scans jax's whole module graph (measured ~70 ms
    pauses, 6x the P99 it lands in); that measures CPython's collector
    defaults, not the dispatch path under test.  Serving deployments
    tune or freeze the GC for exactly this reason."""
    gc.collect()
    was_enabled = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was_enabled:
            gc.enable()


class _Workload:
    """One plan signature's traffic: weights, inputs, reference outputs."""

    def __init__(self, shape: str, r: int, t: int, dtype: str, rng):
        self.spec = StencilSpec(shape, len(GRID), r)
        self.t = t
        self.dtype_name = dtype
        dt = jnp.bfloat16 if dtype == "bfloat16" else np.float32
        self.weights = make_weights(self.spec, seed=r)
        # HOST arrays, like a real serving client would hold: device
        # inputs would make the engine's stack_batch pay one
        # device->host copy per request (and gift the sequential
        # baseline a transfer it never paid for)
        self.xs = [np.asarray(jnp.asarray(rng.normal(size=GRID), dtype=dt))
                   for _ in range(N_INPUTS)]
        self.y_ref = None            # filled by the sequential phase

    @property
    def name(self) -> str:
        return f"{self.spec.name}-t{self.t}-{self.dtype_name}"


def _run_sequential(workloads, n_requests: int):
    """Per-request dispatch: plan lookup + execute + block, one at a time,
    interleaved across signatures.  Also produces the bitwise reference
    outputs (one unbatched plan call per distinct input)."""
    for wl in workloads:                       # warmup: compile + oracle
        plan = stencil_plan(wl.weights, GRID, wl.xs[0].dtype, wl.t)
        wl.y_ref = [np.asarray(jax.block_until_ready(plan(x)))
                    for x in wl.xs]

    hist = LatencyHistogram()
    t0 = time.perf_counter()
    for i in range(n_requests):
        wl = workloads[i % len(workloads)]
        r0 = time.perf_counter()
        plan = stencil_plan(wl.weights, GRID, wl.xs[0].dtype, wl.t)
        jax.block_until_ready(plan(wl.xs[i % N_INPUTS]))
        hist.record(time.perf_counter() - r0)
    wall = time.perf_counter() - t0
    return {"requests": n_requests, "wall_s": wall,
            "requests_per_s": n_requests / wall,
            "latency": hist.snapshot()}


def _run_batched(workloads, n_requests: int):
    """The same traffic through the engine, issued as double-buffered
    bursts: each burst submits one full WINDOW per signature, and two
    bursts stay in flight -- while the client blocks on burst N's
    results (GIL released), the dispatcher executes burst N+1's full
    buckets.  One-future-at-a-time popping measures worse here not
    because the engine is slower but because the client's per-result
    GIL wakeups starve the dispatcher and leave drains half-full.
    Returns the metrics snapshot plus the bitwise verdict."""
    per_sig = n_requests // len(workloads)
    rounds = per_sig // WINDOW
    # buckets pin the launch size to the window; max_batch is the drain's
    # fill target, so it counts the whole interleaved queue -- one window
    # PER signature -- or mixed drains would split into half-empty buckets
    with StencilServer(buckets=(WINDOW,),
                       max_batch=WINDOW * len(workloads)) as server:
        # warmup: one full window per signature compiles the batched plan
        done = [server.submit(wl.weights, wl.xs[i % N_INPUTS], t=wl.t)
                for wl in workloads for i in range(WINDOW)]
        for fut in done:
            fut.result()
        server.metrics.reset()                 # keep plans, drop the stats

        pending = deque()
        results = []
        issued = 0
        t0 = time.perf_counter()
        while issued < rounds or pending:
            while issued < rounds and len(pending) < 2:
                base = issued * WINDOW
                pending.append(
                    [(k, base + j,
                      server.submit(wl.weights,
                                    wl.xs[(base + j) % N_INPUTS],
                                    t=wl.t))
                     for k, wl in enumerate(workloads)
                     for j in range(WINDOW)])
                issued += 1
            for k, i, fut in pending.popleft():
                results.append((k, i, fut.result()))
        wall = time.perf_counter() - t0
        snap = server.stats()
    # bitwise audit OUTSIDE the timed window (the comparisons are host
    # work the serving path never does)
    bitwise = all(
        np.array_equal(np.asarray(y), workloads[k].y_ref[i % N_INPUTS])
        for k, i, y in results)
    snap["wall_s"] = wall
    snap["bitwise_match"] = bitwise
    return snap


def run(quick: bool) -> list[str]:
    sig_defs = SIGS_QUICK if quick else SIGS_FULL
    per_sig = REQS_QUICK if quick else REQS_FULL
    rng = np.random.default_rng(0)
    workloads = [_Workload(*s, rng) for s in sig_defs]
    n_requests = per_sig * len(workloads)

    # Two alternating measurement passes, best-of per side: a background
    # scheduling burst that lands inside ONE phase's window cannot flip
    # the comparison (slow-moving machine noise already hits both phases
    # of a pass equally).  The bitwise audit must hold on every pass.
    pc0 = plan_cache_stats()
    seq_passes, bat_passes = [], []
    for _ in range(2):
        with _gc_quiesced():
            seq_passes.append(_run_sequential(workloads, n_requests))
        with _gc_quiesced():
            bat_passes.append(_run_batched(workloads, n_requests))
    pc1 = plan_cache_stats()
    seq = max(seq_passes, key=lambda s: s["requests_per_s"])
    batched = max(bat_passes, key=lambda b: b["requests_per_s"])
    batched["bitwise_match"] = all(b["bitwise_match"] for b in bat_passes)

    blat = batched["latency"]
    payload = {
        "quick": quick, "grid": list(GRID), "window": WINDOW,
        "requests_per_signature": per_sig,
        "signatures": [wl.name for wl in workloads],
        "sequential": seq,
        "batched": batched,
        "speedup": batched["requests_per_s"] / seq["requests_per_s"]
                   if seq["requests_per_s"] else 0.0,
        "bitwise_match": batched.pop("bitwise_match"),
        "plan_cache": {
            "before": pc0, "after": pc1,
            "hits_delta": pc1["hits"] - pc0["hits"],
            "misses_delta": pc1["misses"] - pc0["misses"],
        },
        # clean-run contract, same as BENCH_kernels.json: any guard event
        # means a serving batch silently degraded mid-benchmark
        "guard_events": guard_events.snapshot(),
    }
    with open(JSON_PATH, "w") as f:
        json.dump(payload, f, indent=1)

    out = ["serving.metric,seq_rps,batched_rps,speedup,b_p50_ms,b_p99_ms,"
           "occupancy,bitwise"]
    out.append(
        f"serving.{'quick' if quick else 'full'},"
        f"{seq['requests_per_s']:.0f},{batched['requests_per_s']:.0f},"
        f"{payload['speedup']:.2f}x,{blat['p50_ms']:.2f},"
        f"{blat['p99_ms']:.2f},{batched['batch_occupancy']:.2f},"
        f"{'OK' if payload['bitwise_match'] else 'MISMATCH'}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(prog="benchmarks.serving")
    ap.add_argument("--quick", action="store_true",
                    default=bool(os.environ.get("BENCH_QUICK")),
                    help="trimmed sweep (also via BENCH_QUICK=1)")
    args = ap.parse_args(argv)
    from repro.core.envutil import init_compile_cache
    init_compile_cache()
    try:
        with case_budget():
            lines = run(args.quick)
    except CaseTimeout as e:
        print(f"serving: benchmark timed out ({e})", file=sys.stderr)
        raise SystemExit(1)
    print("\n".join(lines))


if __name__ == "__main__":
    main()
