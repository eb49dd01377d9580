"""The program window's reduction (``bench/program_window.py``): on a
synthetic trace whose answer is known by hand, on a small scoped trace
recorded on the chip (``bench/tests/data``), and its silence on a
program without spans of its own."""
import collections
import os
import sys
import types

import pytest

from bench import program_window as pw
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data")
#: Device ops as the TPU trace names them: their HLO text, without the
#: metadata, which only the compiled program's text (``HLO``) holds.
KERNEL = ('%fused_direct.1 = f32[8]{0} custom-call(f32[8]{0} %x), '
          'custom_call_target="tpu_custom_call"')
EXCHANGE = ('%collective-permute-done.2 = f32[4]{0} '
            'collective-permute-done(%cp)')
LOCAL = '%fusion.3 = f32[4]{0} fusion(%a), kind=kLoop'
HLO = """
ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %cp = f32[4]{0} collective-permute-start(%x), metadata={op_name="jit(run)/shard_map/repro.dist.exchange/ppermute"}
  %collective-permute-done.2 = f32[4]{0} collective-permute-done(%cp), metadata={op_name="jit(run)/shard_map/repro.dist.exchange/ppermute"}
  %fusion.3 = f32[4]{0} fusion(%a), kind=kLoop, metadata={op_name="jit(run)/shard_map/repro.dist.local/pad"}
  ROOT %fused_direct.1 = f32[8]{0} custom-call(f32[8]{0} %x), custom_call_target="tpu_custom_call", metadata={op_name="jit(run)/shard_map/repro.dist.local/fused_direct/pallas_call"}
}
"""
SCOPES = {"cp": "repro.dist.exchange",
          "collective-permute-done.2": "repro.dist.exchange",
          "fusion.3": "repro.dist.local", "fused_direct.1": "repro.dist.local"}


def _trace(exchange_1=8):
    # window 0..100 ns, two plan calls.  Device 0: kernels 12..40 and
    # 75..90, an exchange 45..50 and a local op 50..55; scopes inside
    # the kernels, and one compute event outside any kernel (dropped).
    # Device 1: a kernel 0..100 and an exchange of ``exchange_1`` ns.
    dev0 = [(KERNEL, 12, 28), (EXCHANGE, 45, 5), (LOCAL, 50, 5),
            (KERNEL, 75, 15)]
    dev1 = [(KERNEL, 0, 100), (EXCHANGE, 40, exchange_1)]
    scopes = {0: [(pw.ASSEMBLE, 12, 3), (pw.COMPUTE, 15, 20),
                  (pw.ASSEMBLE, 35, 1), (pw.COMPUTE, 76, 12),
                  (pw.COMPUTE, 95, 4)]}
    host = [("bench.window", 0, 100),
            ("bench.plan_call", 0, 10), ("repro.plan.call", 1, 8),
            ("bench.result_wait", 10, 50),
            ("bench.plan_call", 58, 14), ("repro.plan.call", 60, 10)]
    return pw.ProgramTrace(devices={0: dev0, 1: dev1}, scopes=scopes,
                           host=host, kernels={KERNEL})


def test_op_scopes_from_the_program_text():
    assert pw.op_scopes(HLO) == SCOPES
    assert pw.op_scope(KERNEL) is None
    nested = ('%f = f32[] fusion(), metadata={op_name="jit(run)/'
              'repro.dist.local/repro.plan.x/add"}')
    assert pw.op_scope(nested) == "repro.plan.x"


def test_gaps_lie_at_the_innermost_span():
    out = pw.reduce_window(_trace(), chips=1, calls=2)
    ns = 1e-9
    # 0..12: inside repro.plan.call (1..9) in bench.plan_call;
    # 40..45: bench.result_wait; 55..75 (midpoint 65): repro.plan.call
    # nested in bench.plan_call; 90..100: no span but the window.
    assert out["idle_gaps_s"] == pytest.approx({
        "repro.plan.call": (12 + 20) * ns, "bench.result_wait": 5 * ns,
        "bench.window": 10 * ns})


def test_kernel_split_and_dispatch():
    out = pw.reduce_window(_trace(), chips=1, calls=2)
    per_call = 1e-6 / 2                          # ns in the window -> ms
    assert out["kernel_ms_per_call"] == pytest.approx(43 * per_call)
    assert out["kernel.compute_ms_per_call"] == pytest.approx(32 * per_call)
    assert out["substrate.assemble_ms_per_call"] == pytest.approx(
        4 * per_call)
    assert out["substrate.pipeline_ms_per_call"] == pytest.approx(
        7 * per_call)
    assert out["split_over_kernel"] == pytest.approx(1.0)
    assert out["plan_calls_traced"] == 2
    assert out["plan.dispatch_us"] == pytest.approx(9e-3)
    assert out["op_scope_ms_per_call"] == {}        # no program text given


def test_op_scopes_time():
    out = pw.reduce_window(_trace(), chips=1, calls=2, scopes=SCOPES)
    per_call = 1e-6 / 2
    assert out["op_scope_ms_per_call"] == pytest.approx({
        "repro.dist.exchange": 5 * per_call,
        "repro.dist.local": (5 + 28 + 15) * per_call})


def test_exchange_on_the_busiest_chip():
    out = pw.reduce_window(_trace(exchange_1=8), chips=2, calls=2,
                           scopes=SCOPES)
    per_call = 1e-6 / 2
    assert out["dist.exchange_ms_per_call"] == pytest.approx(8 * per_call)
    assert out["op_scope_ms_per_call"]["repro.dist.exchange"] == \
        pytest.approx((5 + 8) / 2 * per_call)


def test_without_device_planes_only_host_numbers():
    t = _trace()
    t.devices, t.scopes = {}, {}
    out = pw.reduce_window(t, chips=1, calls=2)
    assert out["plan.dispatch_us"] == pytest.approx(9e-3)
    assert "kernel_ms_per_call" not in out
    t.host = [e for e in t.host if e[0] != tr.WINDOW_SPAN]
    with pytest.raises(ValueError, match="bench.window"):
        pw.reduce_window(t, chips=1, calls=2)


@pytest.mark.parametrize("scopes", [{}, {0: [], 1: []}])
def test_unscoped_trace_reports_no_split(scopes):
    t = _trace()
    t.scopes = scopes
    out = pw.reduce_window(t, chips=1, calls=2)
    assert "kernel.compute_ms_per_call" not in out
    assert out["kernel_ms_per_call"] == pytest.approx(43 * 1e-6 / 2)


def test_program_without_its_own_spans_reads_nothing(monkeypatch):
    """A program that has no ``repro.core.trace`` (the commits before
    it) gets no window: the readers are silent and nothing raises."""
    import repro.core
    monkeypatch.delattr(repro.core, "trace", raising=False)
    monkeypatch.setitem(sys.modules, "repro.core.trace", None)
    run = types.SimpleNamespace(
        trace=True, cell=types.SimpleNamespace(traffic={"driver": "step"}),
        plans=[object()], notes={})
    assert pw.measure(run) is None
    assert run.notes == {}
    from bench import benchspec
    assert benchspec.reader("plan.dispatch_us")(run) is None
    assert benchspec.reader("plan.compile_s")(run) is None


def test_recorded_scoped_trace():
    """Five calls of a scoped ``fused_direct`` plan (1024^2, t=4: eight
    strips of 128 rows, a ring of 18 blocks each) recorded on one TPU
    v5e with the program's spans: the split adds up to the kernel's
    device time, and the numbers are fixed here."""
    trace = pw.read_xplane(os.path.join(DATA,
                                        "box2d1r_1024_t4_scoped.xplane.pb"))
    assert [k.split(" = ")[0] for k in trace.kernels] == ["%fused_direct.1"]
    assert collections.Counter(e[0] for e in trace.scopes[0]) == {
        pw.COMPUTE: 5 * 8, pw.ASSEMBLE: 5 * 8 * 18}
    out = pw.reduce_window(trace, chips=1, calls=5)
    parts = (out["kernel.compute_ms_per_call"]
             + out["substrate.assemble_ms_per_call"]
             + out["substrate.pipeline_ms_per_call"])
    assert parts == pytest.approx(out["kernel_ms_per_call"], rel=0.01)
    want = {"kernel_ms_per_call": 0.0987454,
            "kernel.compute_ms_per_call": 0.0625056,
            "substrate.assemble_ms_per_call": 0.0010724,
            "substrate.pipeline_ms_per_call": 0.0351674,
            "plan.dispatch_us": 252.648}
    for key, value in want.items():
        assert out[key] == pytest.approx(value, rel=1e-9), key
    assert out["plan_calls_traced"] == 5
