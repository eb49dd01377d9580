"""The reduction from trace to device numbers: on a synthetic trace whose
answer is known by hand, and on a small trace recorded on the chip
(``bench/tests/data``), whose numbers are fixed here."""
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")


#: Device ops as the TPU trace names them: by their HLO text.
KERNEL = ('%run.1 = f32[8]{0} custom-call(f32[8]{0} %x), '
          'custom_call_target="tpu_custom_call"')
KERNEL3 = ('%run.3 = f32[8]{0:T(8)} custom-call(f32[8]{0} %y), '
           'custom_call_target="tpu_custom_call"')
PERMUTE = ('%collective-permute-done.2 = f32[4]{0} '
           'collective-permute-done(%cp), metadata={op_name="ppermute"}')
COPY = '%copy.5 = f32[4]{0} copy(%cpd), metadata={op_name="ppermute"}'


def _trace():
    # window 0..100 ns; device 0: a kernel 10..40, a fusion 35..50, a
    # collective 70..80 (the copy after it is no collective); device 1:
    # a kernel 0..100 (always busy).
    dev0 = [(KERNEL, 10, 30), ("fusion.7", 35, 15), (PERMUTE, 70, 10),
            (COPY, 80, 0), (KERNEL, 120, 5)]      # the last after the window
    dev1 = [(KERNEL3, 0, 100)]
    host = [("bench.window", 0, 100), ("bench.plan_call", 0, 5),
            ("bench.result_wait", 5, 60), ("bench.submit", 66, 2),
            ("bench.idle", 80, 20), ("other", 0, 100)]
    return tr.Trace(devices={0: dev0, 1: dev1}, host=host)


def test_op_names_from_hlo_text():
    assert tr.op_parts(KERNEL) == ("run.1", "custom-call")
    assert tr.op_parts(PERMUTE)[1] == "collective-permute-done"
    assert tr.op_parts("fusion.7") == ("fusion.7", "fusion")
    assert tr._op_key(KERNEL) == "run custom-call"
    assert tr._op_key(PERMUTE) == "collective-permute-done"


def test_synthetic_trace_one_chip():
    s = tr.reduce_trace(_trace(), chips=1)
    ns = 1e-9
    assert s.window_s == pytest.approx(100 * ns)
    assert s.busy_s == pytest.approx((40 + 10) * ns)     # 10..50, 70..80
    assert s.kernel_s == pytest.approx(30 * ns)
    assert s.collective_s == s.collective_s_max == pytest.approx(10 * ns)
    assert s.idle_share() == pytest.approx(50.0)
    # gaps 0..10 (plan_call then result_wait: midpoint 5 -> result_wait),
    # 50..70 (midpoint 60 -> result_wait), 80..100 (idle)
    assert s.idle_s == pytest.approx({"bench.result_wait": 30 * ns,
                                      "bench.idle": 20 * ns})
    bd = s.breakdown()
    assert bd["device_ops"][0] == ["run custom-call", pytest.approx(30 * ns)]
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_synthetic_trace_two_chips_average_and_busiest():
    s = tr.reduce_trace(_trace(), chips=2)
    ns = 1e-9
    assert s.busy_s == pytest.approx((50 + 100) / 2 * ns)
    assert s.kernel_s == pytest.approx((30 + 100) / 2 * ns)
    assert s.collective_s == pytest.approx(5 * ns)
    assert s.collective_s_max == pytest.approx(10 * ns)


def test_trace_without_window_or_devices_raises():
    t = _trace()
    with pytest.raises(ValueError, match="needs 3"):
        tr.reduce_trace(t, chips=3)
    t.host = [e for e in t.host if e[0] != "bench.window"]
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce_trace(t, chips=1)


def test_union_merges_overlaps():
    import numpy as np
    iv = tr._union(np.array([[5, 9], [0, 3], [2, 4], [9, 12]], float))
    assert iv.tolist() == [[0, 4], [5, 12]]
    assert tr._union(np.zeros((0, 2))).shape == (0, 2)


#: Traces recorded on one TPU v5e: five back-to-back calls of the
#: cells' plans (``box2d1r-f32``: 10240^2, t=4, ``fused_direct``;
#: ``star3d1r-f32``: 1024^3, t=2, ``fused_matmul_reuse``), with the
#: benchmark's host spans; and what the reduction must read from them.
RECORDED = {
    "box2d1r_10240_t4.xplane.pb": dict(
        chips=1, window_s=0.050029687, busy_s=0.04802538,
        kernel_s=0.04802538, collective_s=0.0,
        idle_share=4.0062353378305104, kernels=5),
    "star3d1r_1024_t2.xplane.pb": dict(
        chips=1, window_s=1.68343679, busy_s=1.678980364,
        kernel_s=1.678980364, collective_s=0.0,
        idle_share=0.2647219085665786, kernels=5),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_chip_trace(name):
    want = RECORDED[name]
    trace = tr.read_xplane(os.path.join(DATA, name))
    assert len(trace.kernels) == 1
    assert sum(op in trace.kernels for op, _, _ in trace.devices[0]) == \
        want["kernels"]
    s = tr.reduce_trace(trace, want["chips"])
    for key in ("window_s", "busy_s", "kernel_s", "collective_s"):
        assert getattr(s, key) == pytest.approx(want[key], rel=1e-12), key
    assert s.idle_share() == pytest.approx(want["idle_share"], rel=1e-9)
    assert s.breakdown()["device_ops"][0][0] == "run custom-call"
