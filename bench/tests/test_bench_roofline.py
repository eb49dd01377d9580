"""The roofline counts the problem's work, whatever backend does it, at
the published peaks of the chip the run names."""
import json
import types

import numpy as np
import pytest

from bench import benchspec, oracle, roofline
from bench.cell import Run


def test_peaks_table_holds_the_published_v5e_peaks():
    p = roofline.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_kind_raises(tmp_path):
    with pytest.raises(ValueError, match="no peaks"):
        roofline.peaks_for("TPU v99")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"Other chip": {"flops_per_s": 1,
                                               "hbm_bytes_per_s": 1}}))
    with pytest.raises(ValueError, match="TPU v5 lite"):
        roofline.peaks_for("TPU v5 lite", str(path))


def test_work_comes_from_the_problem():
    assert roofline.stencil_work(9, 100, 4, 4) == (7200, 800)
    peaks = {"flops_per_s": 1e3, "hbm_bytes_per_s": 1e2}
    assert roofline.least_time(7200, 800, peaks) == (8.0, "memory")
    assert roofline.least_time(7200, 100, peaks) == (7.2, "compute")


def _run(plan, chips=1):
    cell = types.SimpleNamespace(traffic={"driver": "step"})
    run = Run(cell=cell, seed=0, seconds=1.0, trace=True,
              peaks=roofline.peaks_for("TPU v5 lite"), chips=chips)
    run.t, run.nnz, run.points, run.itemsize = 4, 9, 64 * 256 * chips, 4
    run.plans, run.calls = [plan], 10
    run.device_trace = types.SimpleNamespace(kernel_s=2e-6)
    return run


def test_two_backends_count_the_same_work():
    from repro.kernels import stencil_plan
    w = oracle.make_weights("box", 2, 1)
    direct = stencil_plan(w, (64, 256), np.float32, 4,
                          backend="fused_direct", interpret=True)
    mxu = stencil_plan(w, (64, 256), np.float32, 4,
                       backend="fused_matmul_reuse", interpret=True)
    read_direct = benchspec.reader("fused_direct_roofline")
    read_mxu = benchspec.reader("fused_matmul_reuse_roofline")
    a, b = read_direct(_run(direct)), read_mxu(_run(mxu))
    assert a is not None and a == b
    # least time of 10 calls: 2 * 64 * 256 * 4 bytes at 819 GB/s
    assert a == pytest.approx(100 * 10 * (2 * 64 * 256 * 4 / 819e9) / 2e-6)
    # each reader is silent on the other backend's plan
    assert read_direct(_run(mxu)) is None and read_mxu(_run(direct)) is None


def test_share_is_per_chip_on_a_sharded_grid():
    from repro.kernels import stencil_plan
    w = oracle.make_weights("box", 2, 1)
    plan = stencil_plan(w, (64, 256), np.float32, 4,
                        backend="fused_direct", interpret=True)
    one, four = _run(plan), _run(plan, chips=4)
    read = benchspec.reader("fused_direct_roofline")
    assert read(one) == pytest.approx(read(four))


def test_no_kernel_time_reads_nothing():
    from repro.kernels import stencil_plan
    w = oracle.make_weights("box", 2, 1)
    plan = stencil_plan(w, (64, 256), np.float32, 4,
                        backend="fused_direct", interpret=True)
    run = _run(plan)
    run.device_trace.kernel_s = 0.0
    assert benchspec.reader("fused_direct_roofline")(run) is None
    run.device_trace = None
    assert benchspec.reader("fused_direct_roofline")(run) is None


def test_step_mfu_counts_problem_flops_over_the_window():
    from repro.kernels import stencil_plan
    w = oracle.make_weights("box", 2, 1)
    plan = stencil_plan(w, (64, 256), np.float32, 4,
                        backend="fused_direct", interpret=True)
    run = _run(plan)
    run.window_s = 0.5
    flops = 2 * 9 * 64 * 256 * 4 * 10
    assert benchspec.reader("step_mfu")(run) == pytest.approx(
        100 * flops / (0.5 * 197e12))
