"""Guarded execution layer (DESIGN.md §11): failure taxonomy, degradation
ladder, fault injection, plan-cache integrity under failure, env-knob
hardening, the event ring buffer, and the benchmark case budget."""
import math
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import events
from repro.core.envutil import env_flag, env_int, env_str
from repro.kernels import (GuardedExecutionError, HaloExchangeError,
                           KernelCompileError, NumericalFaultError,
                           PlanBuildError, VmemOverflowError,
                           classify_failure, clear_plan_cache,
                           fallback_ladder, guarded_stencil_plan,
                           plan_cache_stats, stencil_plan)
from repro.kernels import plan as plan_mod
from repro.kernels.ref import stencil_direct_ref
from repro.stencil import StencilSpec, make_weights
from repro.testing import faults


@pytest.fixture(autouse=True)
def _guard_hygiene():
    """Every test starts and ends with no armed faults, an empty event
    log, and a cold plan cache -- guard state is process-global."""
    faults.reset_faults()
    events.clear()
    clear_plan_cache()
    yield
    faults.reset_faults()
    events.clear()
    clear_plan_cache()


W = make_weights(StencilSpec("box", 2, 1), seed=0)
X = np.random.default_rng(0).normal(size=(64, 128)).astype(np.float32)


def _ref(t=2):
    return np.asarray(stencil_direct_ref(jnp.asarray(X), jnp.asarray(W), t))


# ---------------------------------------------------------------------------
# Taxonomy
# ---------------------------------------------------------------------------
class TestTaxonomy:
    @pytest.mark.parametrize("msg,cls", [
        ("INTERNAL: Mosaic failed to compile TPU kernel", KernelCompileError),
        ("RESOURCE_EXHAUSTED: Ran out of memory in memory space vmem",
         VmemOverflowError),
        ("error during ppermute collective", HaloExchangeError),
        ("output contained NaN after step", NumericalFaultError),
        ("XLA lowering failed: unsupported op", KernelCompileError),
    ])
    def test_message_classification(self, msg, cls):
        err = classify_failure(RuntimeError(msg))
        assert isinstance(err, cls)
        assert err.cause == cls.cause
        assert isinstance(err.__cause__, RuntimeError)

    def test_stage_breaks_ties(self):
        blank = RuntimeError("something entirely unrecognized")
        assert isinstance(classify_failure(blank, stage="build"),
                          PlanBuildError)
        assert isinstance(classify_failure(blank, stage="execute"),
                          KernelCompileError)

    def test_already_classified_passes_through(self):
        err = VmemOverflowError("x")
        assert classify_failure(err) is err

    def test_all_causes_distinct(self):
        causes = {c.cause for c in (PlanBuildError, KernelCompileError,
                                    VmemOverflowError, NumericalFaultError,
                                    HaloExchangeError)}
        assert len(causes) == 5


# ---------------------------------------------------------------------------
# Fault harness + env knob parsing (the hardening satellite)
# ---------------------------------------------------------------------------
class TestFaultParsing:
    def test_syntax(self):
        specs = faults.parse_faults("compile, vmem:3, nan:2@1, halo:inf")
        assert [(s.kind, s.times, s.skip) for s in specs] == [
            ("compile", 1, 0), ("vmem", 3, 0), ("nan", 2, 1),
            ("halo", math.inf, 0)]

    @pytest.mark.parametrize("raw", ["bogus", "compile:x", "compile:0",
                                     "vmem:1@-1", "nan:1.5"])
    def test_malformed_terms_raise(self, raw):
        with pytest.raises(ValueError, match="REPRO_FAULTS"):
            faults.parse_faults(raw)

    def test_nth_fire_semantics(self):
        with faults.inject("compile", times=2, skip=1) as spec:
            faults.maybe_fail("compile")              # skipped
            for _ in range(2):
                with pytest.raises(RuntimeError, match="injected"):
                    faults.maybe_fail("compile")
            faults.maybe_fail("compile")              # exhausted
        assert spec.fired == 2 and spec.hits == 4
        faults.maybe_fail("compile")                  # scope ended: no-op

    def test_env_arming(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULTS", "vmem:1")
        faults.reset_faults()
        with pytest.raises(RuntimeError, match="VMEM|vmem"):
            faults.maybe_fail("vmem")
        faults.maybe_fail("vmem")                     # consumed
        assert faults.fault_hits()["vmem"] == 1

    def test_env_malformed_raises_on_use(self, monkeypatch):
        monkeypatch.setenv("REPRO_FAULTS", "garbage:kind")
        faults.reset_faults()
        with pytest.raises(ValueError, match="REPRO_FAULTS"):
            faults.maybe_fail("compile")


class TestEnvKnobs:
    def test_env_int_default_and_parse(self, monkeypatch):
        monkeypatch.delenv("REPRO_TEST_KNOB", raising=False)
        assert env_int("REPRO_TEST_KNOB", 7) == 7
        monkeypatch.setenv("REPRO_TEST_KNOB", "42")
        assert env_int("REPRO_TEST_KNOB", 7) == 42

    @pytest.mark.parametrize("raw", ["", "  ", ])
    def test_env_int_empty_is_unset(self, monkeypatch, raw):
        monkeypatch.setenv("REPRO_TEST_KNOB", raw)
        assert env_int("REPRO_TEST_KNOB", 7) == 7

    @pytest.mark.parametrize("raw,match", [
        ("zero", "integer"), ("8MB", "integer"),
        ("-3", ">= 1"), ("0", ">= 1"),
    ])
    def test_env_int_garbage_and_negative(self, monkeypatch, raw, match):
        monkeypatch.setenv("REPRO_TEST_KNOB", raw)
        with pytest.raises(ValueError, match=match):
            env_int("REPRO_TEST_KNOB", 7)

    def test_shared_helper_backs_the_runtime_knobs(self, monkeypatch):
        # both historical knobs now parse through env_int with the same
        # message shape (the hardening satellite's acceptance)
        from repro.kernels import plan_cache_max, vmem_budget_bytes
        for var, fn in (("REPRO_VMEM_BUDGET", vmem_budget_bytes),
                        ("REPRO_PLAN_CACHE_SIZE", plan_cache_max)):
            monkeypatch.setenv(var, "garbage")
            with pytest.raises(ValueError, match=f"{var} must be an integer"):
                fn()
            monkeypatch.setenv(var, "-1")
            with pytest.raises(ValueError, match=f"{var} must be >= 1"):
                fn()
            monkeypatch.delenv(var)
            assert fn() >= 1

    def test_env_flag(self, monkeypatch):
        monkeypatch.delenv("REPRO_TEST_FLAG", raising=False)
        assert env_flag("REPRO_TEST_FLAG") is False
        for raw, want in (("1", True), ("true", True), ("ON", True),
                          ("0", False), ("no", False)):
            monkeypatch.setenv("REPRO_TEST_FLAG", raw)
            assert env_flag("REPRO_TEST_FLAG") is want
        monkeypatch.setenv("REPRO_TEST_FLAG", "maybe")
        with pytest.raises(ValueError, match="boolean"):
            env_flag("REPRO_TEST_FLAG")

    def test_env_str_strips(self, monkeypatch):
        monkeypatch.setenv("REPRO_TEST_KNOB", "  x  ")
        assert env_str("REPRO_TEST_KNOB") == "x"


# ---------------------------------------------------------------------------
# Event ring buffer
# ---------------------------------------------------------------------------
class TestEventLog:
    def test_bounded_with_drop_accounting(self):
        log = events.EventLog(capacity=4)
        for i in range(10):
            log.record("k", i=i)
        snap = log.snapshot()
        assert len(snap["events"]) == 4
        assert snap["recorded"] == 10 and snap["dropped"] == 6
        assert [e["i"] for e in snap["events"]] == [6, 7, 8, 9]

    def test_kind_filter_and_clear(self):
        events.record("a", v=1)
        events.record("b", v=2)
        assert [e["kind"] for e in events.events("a")] == ["a"]
        events.clear()
        assert events.events() == [] and events.snapshot()["recorded"] == 0


# ---------------------------------------------------------------------------
# Degradation ladder + plan-cache integrity under failure
# ---------------------------------------------------------------------------
class TestLadder:
    def test_ladder_order_terminates_at_reference(self):
        ladder = fallback_ladder()
        assert ladder[0] == "fused_matmul_reuse"
        assert ladder[-1] == "reference"
        assert ladder == ("fused_matmul_reuse", "fused_sparse_matmul",
                          "sparse_matmul", "fused_matmul", "matmul",
                          "fused_direct", "direct", "reference")
        # unknown or unranked names fall back onto the FULL ladder
        assert fallback_ladder(after="no_such_backend") == ladder

    def test_clean_run_is_invisible(self):
        p0 = stencil_plan(W, X.shape, np.float32, 2, backend="fused_direct")
        g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                 backend="fused_direct")
        assert g.plan is p0            # the identical cached plan object
        y = g(jnp.asarray(X))
        assert not g.degraded and g.history == []
        assert events.events() == []
        st = plan_cache_stats()
        assert st["build_failures"] == st["exec_failures"] \
            == st["fallbacks"] == 0
        np.testing.assert_array_equal(np.asarray(y), _ref())
        assert "clean" in g.explain()

    def test_compile_inf_bottoms_out_on_reference_bitwise(self):
        with faults.inject("compile", times=math.inf):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_matmul_reuse")
            y = g(jnp.asarray(X))
        assert g.backend == "reference" and g.degraded
        assert all(h["cause"] == "compile" for h in g.history)
        np.testing.assert_array_equal(np.asarray(y), _ref())
        assert "DEGRADED" in g.explain()

    def test_vmem_degrades_geometry_same_backend(self):
        with faults.inject("vmem", times=1):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_direct")
            y = g(jnp.asarray(X))
        assert g.rung == "fused_direct+degraded"
        assert [h["cause"] for h in g.history] == ["vmem"]
        assert g.backend == "fused_direct"     # same regime, smaller tiles
        np.testing.assert_array_equal(np.asarray(y), _ref())

    def test_user_errors_raise_raw_not_laddered(self):
        with pytest.raises(ValueError, match="fusion depth"):
            guarded_stencil_plan(W, X.shape, np.float32, 0)
        with pytest.raises(ValueError, match="unknown backend"):
            guarded_stencil_plan(W, X.shape, np.float32, 2, backend="nope")
        with pytest.raises(ValueError, match="rank"):
            guarded_stencil_plan(W, (8, 8, 8), np.float32, 2)
        assert events.events() == []           # none of those are failures

    def test_failed_signature_never_in_lru(self):
        """Cache-integrity satellite: after an injected compile fault, the
        LRU must not contain the failed signature, the surviving rung IS
        cached, and the counters stay consistent."""
        with faults.inject("compile", times=1):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_direct")
            g(jnp.asarray(X))
        failed_key = plan_mod.plan_signature(
            W, X.shape, np.float32, 2, backend="fused_direct")[0]
        assert failed_key not in plan_mod._CACHE
        assert plan_mod.failed_plan(failed_key) is not None
        assert g.plan.key in plan_mod._CACHE   # surviving rung cached
        st = plan_cache_stats()
        assert st["exec_failures"] == 1 and st["fallbacks"] == 1
        assert st["negative_size"] == 1
        # misses: failed rung + surviving rung; hits unchanged
        assert st["misses"] >= 2 and st["hits"] == 0

    def test_negative_entry_short_circuits_repeat_failures(self):
        with faults.inject("compile", times=1):
            g1 = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                      backend="fused_direct")
            g1(jnp.asarray(X))
        before = plan_cache_stats()
        # no fault armed now -- but the signature is negative-cached, so
        # the known-bad rung is skipped WITHOUT re-attempting the build
        g2 = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                  backend="fused_direct")
        assert g2.rung == "fused_direct+degraded"
        st = plan_cache_stats()
        assert st["negative_hits"] > before["negative_hits"]
        assert st["exec_failures"] == before["exec_failures"]  # no retry
        assert [e["kind"] for e in events.events()][-1] == "guard_skip"

    def test_negative_entry_expires_after_cache_churn(self, monkeypatch):
        monkeypatch.setenv("REPRO_PLAN_CACHE_SIZE", "3")
        with faults.inject("compile", times=1):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_direct")
            g(jnp.asarray(X))
        failed_key = plan_mod.plan_signature(
            W, X.shape, np.float32, 2, backend="fused_direct")[0]
        assert plan_mod.failed_plan(failed_key) is not None
        # churn the cache past the bound: 4 fresh signatures > 3
        for t in (3, 4, 5, 6):
            stencil_plan(W, X.shape, np.float32, t, backend="reference")
        assert plan_mod.failed_plan(failed_key) is None   # expired
        # and the rung is attemptable again (no fault armed -> it builds)
        g2 = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                  backend="fused_direct")
        assert not g2.degraded

    def test_watchdog_recovers_step_and_demotes(self):
        with faults.inject("nan", times=1):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_direct", watchdog=True)
            y = g(jnp.asarray(X))
        assert [h["cause"] for h in g.history] == ["numerical"]
        assert events.events("guard_watchdog")
        np.testing.assert_array_equal(np.asarray(y), _ref())
        # demoted rung keeps serving oracle-grade output
        np.testing.assert_array_equal(np.asarray(g(jnp.asarray(X))), _ref())

    def test_watchdog_off_by_default_lets_nan_through(self):
        with faults.inject("nan", times=1):
            g = guarded_stencil_plan(W, X.shape, np.float32, 2,
                                     backend="fused_direct")
            y = g(jnp.asarray(X))
        assert not g.degraded
        assert np.isnan(np.asarray(y)).any()   # opt-in means OPT-IN

    def test_guarded_apply_wrapper(self):
        from repro.kernels import stencil_apply
        with faults.inject("compile", times=1):
            y = stencil_apply(jnp.asarray(X), W, t=2, backend="fused_direct",
                              guard=True)
        np.testing.assert_array_equal(np.asarray(y), _ref())
        assert plan_cache_stats()["fallbacks"] == 1


# ---------------------------------------------------------------------------
# Benchmark case budget
# ---------------------------------------------------------------------------
class TestCaseBudget:
    def test_trips_on_overrun(self):
        from benchmarks.timing import CaseTimeout, case_budget
        t0 = time.perf_counter()
        with pytest.raises(CaseTimeout):
            with case_budget(1):
                time.sleep(5)
        assert time.perf_counter() - t0 < 4

    def test_no_trip_within_budget_and_alarm_restored(self):
        import signal
        from benchmarks.timing import case_budget
        with case_budget(30):
            pass
        assert signal.getitimer(signal.ITIMER_REAL)[0] == 0

    def test_zero_disables(self, monkeypatch):
        from benchmarks.timing import bench_budget_s, case_budget
        monkeypatch.setenv("REPRO_BENCH_BUDGET_S", "0")
        assert bench_budget_s() == 0
        with case_budget():
            time.sleep(0.01)               # no alarm armed at all

    def test_nested_budget_defers_to_outer(self):
        import signal
        from benchmarks.timing import CaseTimeout, case_budget
        with pytest.raises(CaseTimeout):
            with case_budget(1):
                outer = signal.getitimer(signal.ITIMER_REAL)[0]
                assert outer > 0
                with case_budget(1000):    # must NOT cancel the outer timer
                    assert signal.getitimer(signal.ITIMER_REAL)[0] > 0
                    time.sleep(5)

    def test_garbage_env_budget_raises(self, monkeypatch):
        from benchmarks.timing import bench_budget_s
        monkeypatch.setenv("REPRO_BENCH_BUDGET_S", "soon")
        with pytest.raises(ValueError, match="REPRO_BENCH_BUDGET_S"):
            bench_budget_s()
