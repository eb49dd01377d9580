"""The high-radius box cell, ``box2d3r-f32.step``, rehearsed end to end
on the CPU at a tiny size as ``test_bench_cells.py`` rehearses the
others: correct as it stands, not correct with the control or with the
timed path broken underneath, and its traced run reports every metric
``BENCHMARK.json`` lists for it, ``direct_roofline`` among them.  Last,
that reader reads a ``direct`` plan and no other."""
import time
import types

import numpy as np
import pytest

from bench import benchspec, oracle, roofline
from bench import run as bench_run
from bench.cell import Run
from bench.tests.test_bench_cells import (  # noqa: F401 -- fixtures
    FAULTS, SECONDS, clean_program, env)

CELL = "box2d3r-f32.step"
#: A tiny stand-in for the 10240^2 grid; bands of 16 rows keep the
#: banded oracle's 3-row halo in play.
TINY = {"grid": [64, 256], "oracle_band": 16}


def _drive(env, trace=False):
    cell = benchspec.resolve(CELL)
    cell.config.update(TINY)
    env.t_start = time.perf_counter()
    return bench_run.run_cell(cell, 2**31 + 15, SECONDS, trace, env)


def test_cell_runs_correct(env, clean_program):
    before = env.compiles()
    out = _drive(env)
    assert env.compiles() > before
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"gstencil_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["checks"]["compiles_in_window"]["value"] == 0


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_timed_path_is_not_correct(env, fault, monkeypatch,
                                          clean_program):
    from repro.kernels.plan import StencilPlan
    monkeypatch.setattr(StencilPlan, "__call__",
                        FAULTS[fault](StencilPlan.__call__))
    out = _drive(env)
    assert not out["correct"]
    rel = out["checks"]["rel_err"]
    assert rel["value"] > rel["limit"]


def test_traced_run_reports_per_layer_metrics(env, monkeypatch,
                                              clean_program):
    """The CPU trace has no TPU plane: the harness is handed a
    recorded-style summary, and every listed reader then reports."""
    from bench import trace_reduce

    class FakeTracer:
        def stop(self):
            return trace_reduce.TraceSummary(
                chips=1, window_s=SECONDS, busy_s=SECONDS,
                kernel_s=SECONDS, collective_s=0.0, collective_s_max=0.0,
                ops_s={"direct custom-call": SECONDS}, idle_s={})

    monkeypatch.setattr(env, "start_trace", FakeTracer)
    out = _drive(env, trace=True)
    assert out["correct"]
    listed = {m["name"] for m in benchspec.resolve(CELL).per_layer}
    assert "direct_roofline" in listed
    assert set(out["metrics"]) == listed
    assert out["metrics"]["substrate.read_amp"]["value"] == 1.25
    assert 0 < out["metrics"]["direct_roofline"]["value"] < 100


def _run(plan):
    cell = types.SimpleNamespace(traffic={"driver": "step"})
    run = Run(cell=cell, seed=0, seconds=1.0, trace=True,
              peaks=roofline.peaks_for("TPU v5 lite"), chips=1)
    run.t, run.nnz, run.points, run.itemsize = 1, 49, 64 * 256, 4
    run.plans, run.calls = [plan], 10
    run.device_trace = types.SimpleNamespace(kernel_s=2e-6)
    return run


def test_direct_roofline_reads_only_a_direct_plan():
    from repro.kernels import stencil_plan
    w = oracle.make_weights("box", 2, 3)
    direct = stencil_plan(w, (64, 256), np.float32, 1, backend="direct",
                          interpret=True)
    fused = stencil_plan(w, (64, 256), np.float32, 1,
                         backend="fused_direct", interpret=True)
    read = benchspec.reader("direct_roofline")
    # least time of 10 calls: 2 * 64 * 256 * 4 bytes at 819 GB/s bounds
    # 2 * 49 * 64 * 256 FLOPs at 197 TFLOP/s
    assert read(_run(direct)) == pytest.approx(
        100 * 10 * (2 * 64 * 256 * 4 / 819e9) / 2e-6)
    assert read(_run(fused)) is None
    assert benchspec.reader("fused_direct_roofline")(_run(direct)) is None
