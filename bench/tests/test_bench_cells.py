"""Each cell rehearsed end to end on the CPU at a tiny size, with the
Pallas kernels in interpret mode (steered here, through the harness's
``Env``, and not through any option of the program): the run is correct
as it stands, and comes out not correct with the control (the oracle in
bfloat16 in the program's place) or with the timed path broken
underneath in each way the cell can break.  The sharded cell runs on
four virtual CPU devices in a child process.  Last, ``run.py`` refuses
to run without a TPU, or without the program beside it."""
import json
import os
import shutil
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import pytest

from bench import benchspec, oracle, roofline
from bench import run as bench_run

ROOT = benchspec.ROOT

#: The cells rehearsed here -- those of BENCHMARK.json and those still
#: to be admitted -- as (config, traffic, chips), with tiny stand-ins
#: for their sizes (configuration, traffic).
CELLS = {
    "box2d1r-f32.step": ("box2d1r-f32", "step", 1),
    "star3d1r-f32.step": ("star3d1r-f32", "step", 1),
    "box2d1r-f32.ensemble": ("box2d1r-f32", "ensemble", 1),
    "box2d1r-f32-2x2.step": ("box2d1r-f32-2x2", "step", 4),
}
TINY = {
    "box2d1r-f32.step": ({"grid": [64, 256], "oracle_band": 16}, {}),
    "star3d1r-f32.step": ({"grid": [16, 16, 128], "oracle_band": 4}, {}),
    "box2d1r-f32.ensemble": ({}, {"grid": [32, 128], "rate_per_s": 200,
                                  "pool": 8}),
}
SECONDS = 0.3


def load(name):
    """The cell ``name`` with the metrics BENCHMARK.json gives it."""
    config, traffic, chips = CELLS[name]
    entry = {"name": name, "config": config, "traffic": traffic,
             "chips": chips}
    return benchspec.load_cell(entry, f"bench/configs/{config}.json",
                               benchspec.load_benchmark())


@pytest.fixture(scope="module")
def env():
    from repro.core import perfmodel as pm
    return bench_run.Env(jax.devices(), pm.TPU_V5E_BF16,
                         roofline.peaks_for("TPU v5 lite"),
                         time.perf_counter(), allow_interpret=True)


def _tiny(name, **traffic):
    cell = load(name)
    cfg, mix = TINY[name]
    cell.config.update(cfg)
    cell.traffic.update(mix)
    cell.traffic.update(traffic)
    return cell


def _drive(env, name, trace=False, **traffic):
    env.t_start = time.perf_counter()
    return bench_run.run_cell(_tiny(name, **traffic), 2**31 + 11, SECONDS,
                              trace, env)


@pytest.fixture
def clean_program():
    """Plans and guard events start empty: a test that breaks the timed
    path must build its plans through the broken path."""
    from repro.core import events
    from repro.kernels import clear_plan_cache
    clear_plan_cache()
    events.clear()
    yield
    clear_plan_cache()
    events.clear()


@pytest.mark.parametrize("name", sorted(TINY))
def test_cell_runs_correct(env, name, clean_program):
    before = env.compiles()
    out = _drive(env, name)
    assert env.compiles() > before          # set-up compiled, and it counts
    assert out["correct"], out["checks"]
    assert list(out["checks"])[-1] == "failed_requests"
    assert list(out)[-1] == "checks"
    cell = load(name)
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["checks"]["compiles_in_window"]["value"] == 0


def _bf16_oracle(self, x):
    modes = tuple(self.boundary)
    w = jnp.asarray(self.weights, jnp.bfloat16)

    def one(g):
        return oracle.apply_stencil_steps(g.astype(jnp.bfloat16), w, self.t,
                                          modes).astype(g.dtype)

    return jax.vmap(one)(x) if self.batch is not None else one(x)


def _unchanged(self, x):
    return x


def _altered(orig):
    def call(self, x):
        y = orig(self, x)
        return y.at[(0,) * y.ndim].add(1.0)
    return call


def _half_batch(orig):
    def call(self, x):
        y = orig(self, x)
        if self.batch is None:
            return y
        return y.at[(self.batch + 1) // 2:].set(0.0)
    return call


FAULTS = {
    "control_bf16": lambda orig: _bf16_oracle,
    "state_unchanged": lambda orig: _unchanged,
    "answer_altered": _altered,
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", sorted(TINY))
def test_broken_timed_path_is_not_correct(env, name, fault, monkeypatch,
                                          clean_program):
    from repro.kernels.plan import StencilPlan
    orig = StencilPlan.__call__
    monkeypatch.setattr(StencilPlan, "__call__", FAULTS[fault](orig))
    out = _drive(env, name)
    assert not out["correct"]
    rel = out["checks"]["rel_err"]
    assert rel["value"] > rel["limit"]


def test_half_batch_left_out_is_not_correct(env, monkeypatch,
                                            clean_program):
    """Half of each batch's slots answered from nothing: at a rate the
    interpreter cannot keep up with, batches hold many requests."""
    from repro.kernels.plan import StencilPlan
    monkeypatch.setattr(StencilPlan, "__call__",
                        _half_batch(StencilPlan.__call__))
    out = _drive(env, "box2d1r-f32.ensemble", rate_per_s=3000)
    assert not out["correct"]
    assert out["checks"]["rel_err"]["value"] > \
        out["checks"]["rel_err"]["limit"]


def test_traced_run_reports_per_layer_metrics(env, monkeypatch,
                                              clean_program):
    """A traced run on the CPU: the trace holds no TPU plane, so the
    harness is handed a recorded-style summary; the readers then report
    what the cell lists."""
    from bench import trace_reduce

    class FakeTracer:
        def __init__(self, chips):
            self.chips = chips

        def stop(self):
            return trace_reduce.TraceSummary(
                chips=self.chips, window_s=SECONDS, busy_s=SECONDS / 2,
                kernel_s=SECONDS / 3, collective_s=0.0,
                collective_s_max=0.0, ops_s={"custom-call": SECONDS / 3},
                idle_s={"bench.result_wait": SECONDS / 2})

    monkeypatch.setattr(env, "start_trace", lambda: FakeTracer(1))
    out = _drive(env, "box2d1r-f32.step", trace=True)
    assert out["correct"]
    listed = {m["name"] for m in load("box2d1r-f32.step").per_layer}
    # the plan is not sharded, so the collective reader stays silent
    assert set(out["metrics"]) == listed - {"dist.collective_ms_per_call"}
    assert out["metrics"]["device.idle_share.step"]["value"] == \
        pytest.approx(50.0)
    assert out["device"]["busy_s"] == pytest.approx(SECONDS / 2)
    assert out["breakdown"]["idle_gaps"][0][0] == "bench.result_wait"


SHARDED = textwrap.dedent("""
    import json, sys, time
    sys.path.insert(0, {root!r}); sys.path.insert(0, {src!r})
    sys.path.insert(0, {tests!r})
    import jax, jax.numpy as jnp
    from test_bench_cells import load
    from bench import benchspec, roofline, run as R
    from repro.core import perfmodel as pm
    from repro.kernels import clear_plan_cache
    import repro.stencil.distributed as dist
    env = R.Env(jax.devices(), pm.TPU_V5E_BF16,
                roofline.peaks_for("TPU v5 lite"), time.perf_counter(),
                allow_interpret=True)

    def cell():
        c = load("box2d1r-f32-2x2.step")
        c.config.update(grid=[128, 256], oracle_band=16)
        return c

    out = {{}}
    sound = R.run_cell(cell(), 5, 0.3, False, env)
    out["sound"] = sound["correct"], sound["checks"]["rel_err"]

    from bench import trace_reduce

    class FakeTracer:
        def stop(self):
            return trace_reduce.TraceSummary(
                chips=4, window_s=0.3, busy_s=0.25, kernel_s=0.2,
                collective_s=0.01, collective_s_max=0.02, ops_s={{}},
                idle_s={{}})

    env.start_trace = FakeTracer
    traced = R.run_cell(cell(), 6, 0.3, True, env)
    out["traced"] = sorted(traced["metrics"])
    from bench.cell import Run, run_step
    run = run_step(env, Run(cell=cell(), seed=7, seconds=0.3, trace=True,
                            peaks=env.peaks, chips=4))
    out["readers"] = {{name: benchspec.reader(name)(run) for name in (
        "substrate.read_amp", "dist.collective_ms_per_call",
        "fused_direct_roofline", "device.idle_share.step")}}
    out["audit_ok"] = run.notes["read_amp_audit_ok"]

    def no_exchange(x, dim, radius, axis_name):
        pad = [(0, 0)] * x.ndim
        pad[dim] = (radius, radius)
        return jnp.pad(x, pad, mode="wrap")

    clear_plan_cache()
    dist._halo_exchange_dim = no_exchange
    broken = R.run_cell(cell(), 5, 0.3, False, env)
    out["no_exchange"] = broken["correct"], broken["checks"]["rel_err"]
    print(json.dumps(out))
""")


def test_sharded_cell_on_four_virtual_devices(tmp_path):
    script = tmp_path / "sharded.py"
    script.write_text(SHARDED.format(root=ROOT,
                                     src=os.path.join(ROOT, "src"),
                                     tests=os.path.dirname(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    ok, rel = out["sound"]
    assert ok and rel["value"] <= rel["limit"]
    ok, rel = out["no_exchange"]
    assert not ok and rel["value"] > rel["limit"]
    listed = {m["name"] for m in load("box2d1r-f32-2x2.step").per_layer}
    assert set(out["traced"]) == listed
    readers = out["readers"]
    assert all(v is not None for v in readers.values()), readers
    assert readers["dist.collective_ms_per_call"] > 0
    assert out["audit_ok"]


def _run_py(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "box2d1r-f32.step",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_without_a_tpu():
    proc = _run_py(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "needs a TPU" in proc.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


@pytest.mark.parametrize("limit, grid_bytes, want", [
    (None, 1 << 30, 64),             # no memory limit reported: the most
    (16 << 30, 4 << 30, 1),          # a grid of a quarter of the chip
    (16 << 30, 400 << 20, 22),       # 0.6 of 16 GiB holds 24, less 2
    (16 << 30, 1 << 20, 64),         # a tiny grid: capped
])
def test_calls_ahead_fit_the_chip(limit, grid_bytes, want):
    import types
    from bench.cell import calls_ahead
    stats = None if limit is None else {"bytes_limit": limit}
    device = types.SimpleNamespace(memory_stats=lambda: stats)
    mix = benchspec._load_json(ROOT, "bench/traffic/step.json")
    assert calls_ahead(mix, grid_bytes, device) == want
