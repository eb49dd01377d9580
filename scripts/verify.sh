#!/usr/bin/env bash
# Tier-1 verification: the test suite plus the benchmark harness in
# interpret mode (no TPU required).  Run from anywhere:
#
#   scripts/verify.sh            # quick benchmark sweep (BENCH_QUICK=1)
#   BENCH_FULL=1 scripts/verify.sh   # full Box/Star x r x t traffic grid
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS="${JAX_PLATFORMS:-cpu}"
if [ -z "${BENCH_FULL:-}" ]; then
  export BENCH_QUICK=1
fi

# Every REPRO_* env knob must be consumed through core/envutil (one
# parser, loud failures); cheap AST lint, so it runs before anything else.
python scripts/lint_env.py

# Tier-1 (ROADMAP.md).  The seed test debt is zero: any failure is a real
# regression, so fail fast before the benchmark smoke.
python -m pytest -x -q

# Static plan audit (DESIGN.md §13): prove the analytic traffic/FLOP
# model against the launch structure of every non-legacy backend across
# the 1D/2D/3D x remainder-width matrix.  Exits nonzero on any violation.
python scripts/audit.py

# Stamp the harness start so the serving gate below can prove its JSON was
# produced by THIS run.  (The traffic harness no longer needs the mtime
# inference: benchmarks/run.py writes a per-run manifest, gated by name.)
BENCH_STAMP="$(mktemp)"
export BENCH_STAMP

python benchmarks/run.py

# The harness finishes the sweep past a failed module, then exits 1 (which
# stops this script); the manifest it writes names every failed module.
python - <<'EOF'
import json
with open("BENCH_run.json") as f:
    manifest = json.load(f)
failed = manifest["failed"]
assert not failed, f"benchmark module(s) failed: {', '.join(failed)}"
assert "traffic" in manifest["succeeded"], \
    "traffic module missing from the benchmark manifest"
print(f"verify: {len(manifest['succeeded'])} benchmark modules OK")
EOF

# The benchmark smoke must include at least one freshly measured 3D
# halo-plane traffic case (DESIGN.md §9), with the sub-blocked
# amplification strictly below the whole-slab foil's 9x (the ISSUE-4
# acceptance criterion), and at least one wide-grid column-tiled case
# (DESIGN.md §10) whose read amplification stays below the whole-width
# 3x foil with a genuinely positive resolved w_tile (ISSUE-5).
python - <<'EOF'
import json, os
path = "BENCH_kernels.quick.json" if os.environ.get("BENCH_QUICK") \
    else "BENCH_kernels.json"
with open(path) as f:
    data = json.load(f)
# Per-case wall-clock budgets (benchmarks/timing.case_budget) may record
# a case as timed_out instead of wedging the run; tolerate those rows but
# require the surviving measurements to be non-empty.
timed_out = [c["case"] for group in ("cases", "cases_3d", "cases_wide")
             for c in data[group] if c.get("timed_out")]
if timed_out:
    print(f"verify: WARNING {len(timed_out)} case(s) timed out: {timed_out}")
cases = [c for c in data["cases_3d"] if not c.get("timed_out")]
assert cases, f"no (surviving) 3D traffic cases in {path}"
for c in cases:
    assert c["read_bytes_step_direct_subblocked"] < \
        c["read_bytes_step_direct_wholestrip"], c["case"]
    assert c["read_amp_subblocked"] < c["read_amp_wholestrip"], c["case"]
# Sparse-compaction gate (DESIGN.md §14): every 2D and 3D case records
# the star-vs-box sparsity sweep.  The compacted contraction must be
# bitwise-equal to the dense reuse plan everywhere; star cases must
# execute strictly fewer MXU FLOPs per step (kept-row fraction S < 1),
# box cases exactly the dense count (S = 1 -- no structural zeros to
# drop).  At least one star case must survive in each rank.
sparse2d = [c for c in data["cases"]
            if not c.get("timed_out") and "kept_row_fraction" in c]
sparse = sparse2d + cases
assert any(c["shape"] == "star" for c in sparse2d), \
    "no surviving 2D star case for the sparse sweep"
assert any(c["shape"] == "star" for c in cases), \
    "no surviving 3D star case for the sparse sweep"
for c in sparse:
    assert c["sparse_bitwise_equal"], \
        f"sparse output diverged from dense: {c['case']}"
    if c["shape"] == "star":
        assert c["mxu_flops_step_sparse"] < c["mxu_flops_step_dense"], \
            (f"star case {c['case']} did not shrink MXU FLOPs: "
             f"{c['mxu_flops_step_sparse']} !< {c['mxu_flops_step_dense']}")
        assert c["kept_row_fraction"] < 1.0, c["case"]
    else:
        assert c["mxu_flops_step_sparse"] == c["mxu_flops_step_dense"], \
            f"box case {c['case']} changed MXU FLOPs under compaction"
        assert c["kept_row_fraction"] == 1.0, c["case"]
# Boundary-mode rows (DESIGN.md §15): every timed mode must match its
# mode-matched oracle, and the distributed overlap pair must be
# bitwise-equal to the serialized foil with a nonzero interleave
# counter (the timing comparison itself is recorded, not gated -- CPU
# wall-clock is too noisy for CI).
bnd = [c for c in data.get("cases_boundary", []) if not c.get("timed_out")]
assert bnd, f"no (surviving) boundary-mode cases in {path}"
for c in bnd:
    assert c["oracle_max_err"] < 5e-4, (c["case"], c["oracle_max_err"])
ov = data.get("halo_overlap", {})
if "us_step_overlap" in ov:
    assert ov["bitwise_equal"], "overlap stepper != serialized foil"
    assert ov["interleave_counters"]["interior_before_recv_consumed"] > 0
wide = [c for c in data["cases_wide"] if not c.get("timed_out")]
assert wide, f"no (surviving) wide-grid column-tiled cases in {path}"
for c in wide:
    assert c["w_tile"] > 0 and c["w_block"] > 0, c["case"]
    assert c["read_amp_coltiled"] < c["read_amp_wholestrip"], c["case"]
    assert c["read_bytes_step_direct_coltiled"] < \
        c["read_bytes_step_direct_wholestrip"], c["case"]
# A clean run must degrade NOTHING: the guard layer's event log (dumped
# into the JSON by benchmarks/traffic.py) has to be empty -- any entry
# means a kernel failed and silently fell down the degradation ladder.
guard = data.get("guard_events", {})
assert guard.get("events", []) == [], \
    f"guard events on a clean run: {guard['events']}"
assert guard.get("dropped", 0) == 0, "guard event ring buffer overflowed"
stats = data.get("plan_stats", {})
for k in ("build_failures", "exec_failures", "fallbacks"):
    assert stats.get(k, 0) == 0, f"clean run but plan_stats[{k!r}]={stats[k]}"
n_star = sum(c["shape"] == "star" for c in sparse)
print(f"verify: {len(cases)} 3D traffic case(s) in {path}, "
      "sub-blocked < whole-slab; "
      f"{len(wide)} wide case(s), column-tiled < whole-width foil; "
      f"{len(sparse)} sparse case(s) bitwise-equal "
      f"({n_star} star < dense MXU FLOPs); "
      f"{len(bnd)} boundary case(s) oracle-matched; guard event log clean")
EOF

# Serving gate (DESIGN.md §12): the batched engine must beat per-request
# dispatch on identical traffic, bitwise-equal, with P50/P99 freshly
# measured into BENCH_serving.json, and the plan cache must prove the
# sharing contract -- at least (requests - distinct signatures) hits.
python benchmarks/serving.py ${BENCH_QUICK:+--quick}

python - <<'EOF'
import json, os
path = "BENCH_serving.json"
assert os.path.getmtime(path) >= os.path.getmtime(os.environ["BENCH_STAMP"]), \
    f"{path} was not rewritten by this run (serving benchmark failed?)"
with open(path) as f:
    d = json.load(f)
seq, bat = d["sequential"], d["batched"]
assert bat["requests_per_s"] > seq["requests_per_s"], \
    (f"batched engine lost to per-request dispatch: "
     f"{bat['requests_per_s']:.0f} <= {seq['requests_per_s']:.0f} req/s")
assert d["bitwise_match"], "batched responses diverged from unbatched plans"
lat = bat["latency"]
for k in ("p50_ms", "p99_ms"):
    assert lat.get(k, 0) > 0, f"batched latency {k} missing or zero"
assert bat["failed"] == 0, f"{bat['failed']} serving request(s) failed"
assert bat["responded"] == bat["submitted"], \
    f"lost requests: responded {bat['responded']} != submitted {bat['submitted']}"
# Plan-sharing contract: every request past the first per signature must
# hit the cache (sequential side alone guarantees this many hits; the
# engine's (signature, bucket) plans add more).
pc = d["plan_cache"]
need = seq["requests"] - len(d["signatures"])
assert pc["hits_delta"] >= need, \
    f"plan cache hits {pc['hits_delta']} < requests - signatures = {need}"
guard = d["guard_events"]
assert guard.get("events", []) == [], \
    f"serving batch degraded on a clean run: {guard['events']}"
assert guard.get("dropped", 0) == 0, "guard event ring buffer overflowed"
print(f"verify: serving {bat['requests_per_s']:.0f} req/s batched vs "
      f"{seq['requests_per_s']:.0f} sequential ({d['speedup']:.2f}x), "
      f"P50 {lat['p50_ms']:.1f} ms P99 {lat['p99_ms']:.1f} ms, "
      f"{pc['hits_delta']} plan-cache hits, bitwise OK")
EOF
rm -f "$BENCH_STAMP"
