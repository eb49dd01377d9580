"""The program's own trace spans and counters (``repro.core.trace``):
plan build and call spans in the profiler trace, the compile counter's
attribution to the plan whose call compiled, and the sharded stepper's
exchange and local scopes in the ops' metadata.  The kernels' names and
in-kernel scopes are checked where the kernels compile for a described
chip, ``tests/test_tpu_compile.py``."""
import glob
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import trace
from repro.kernels import clear_plan_cache, plan_cache_stats, stencil_plan
from repro.stencil import StencilSpec, make_weights

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


@pytest.fixture
def weights():
    return make_weights(StencilSpec("box", 2, 1), seed=0)


def test_scope_switch_is_off_by_default_and_restored():
    assert trace.kernel_scopes() is False
    with trace.kernel_scopes_on():
        assert trace.kernel_scopes() is True
        with trace.kernel_scopes_on(False):
            assert trace.kernel_scopes() is False
        assert trace.kernel_scopes() is True
    assert trace.kernel_scopes() is False


def test_scoped_plan_never_aliases_unscoped(weights):
    plain = stencil_plan(weights, (32, 128), np.float32, 2)
    with trace.kernel_scopes_on():
        scoped = stencil_plan(weights, (32, 128), np.float32, 2)
    again = stencil_plan(weights, (32, 128), np.float32, 2)
    assert scoped is not plain and scoped.key != plain.key
    assert again is plain
    assert scoped.ctx.scopes and not plain.ctx.scopes
    assert plain.ctx.name == plain.backend
    x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 128)),
                    jnp.float32)
    np.testing.assert_array_equal(np.asarray(scoped(x)),
                                  np.asarray(plain(x)))


def test_compile_counter_attributes_to_the_calling_plan(weights):
    clear_plan_cache()
    x = jnp.ones((32, 128), jnp.float32)
    first = stencil_plan(weights, (32, 128), np.float32, 1,
                         use_cache=False)
    other = stencil_plan(weights, (32, 256), np.float32, 1,
                         use_cache=False)
    assert first.compiles == 0 and first.compile_s == 0.0
    y = first(x)
    assert first.compiles == 1 and first.compile_s > 0
    first(y).block_until_ready()            # a second call compiles nothing
    assert first.compiles == 1 and other.compiles == 0
    other(jnp.ones((32, 256), jnp.float32))
    assert other.compiles == 1 and first.compiles == 1
    jax.jit(lambda a: a * 3.0)(x)           # not a plan call: not counted
    stats = plan_cache_stats()
    assert stats["compiles"] == 2
    assert stats["compile_s"] == pytest.approx(first.compile_s
                                               + other.compile_s)


def test_plan_spans_land_in_the_profiler_trace(weights, tmp_path):
    x = jnp.ones((32, 128), jnp.float32)
    jax.profiler.start_trace(str(tmp_path))
    try:
        plan = stencil_plan(weights, (32, 128), np.float32, 3,
                            use_cache=False)
        for _ in range(3):
            x = plan(x)
        x.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" /
                            "*.xplane.pb"))
    names = [e.name for p in jax.profiler.ProfileData.from_file(path).planes
             for line in p.lines for e in line.events]
    assert names.count(trace.PLAN_BUILD) == 1
    assert names.count(trace.PLAN_CALL) == 3


def test_dist_scopes_reach_op_metadata():
    """On four virtual devices, the sharded plan's compiled program names
    its halo permutes under ``repro.dist.exchange`` and its local kernel
    apply under ``repro.dist.local``."""
    code = textwrap.dedent("""
        import json, re
        import jax, numpy as np, jax.numpy as jnp
        from jax.sharding import Mesh
        from repro.kernels import stencil_plan
        from repro.stencil import StencilSpec, make_weights
        mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("x", "y"))
        w = make_weights(StencilSpec("box", 2, 1), seed=0)
        plan = stencil_plan(w, (64, 256), np.float32, 2, mesh=mesh,
                            shard_spec=("x", "y"), dist_mode="fused",
                            backend="fused_direct", interpret=True)
        text = plan.fn.lower(jax.ShapeDtypeStruct(
            (64, 256), jnp.float32)).compile().as_text()
        permutes = [l for l in text.splitlines()
                    if "collective-permute" in l and "op_name" in l]
        names = re.findall(r'op_name="([^"]*)"', text)
        print(json.dumps({
            "permutes": len(permutes),
            "permutes_in_exchange": sum("repro.dist.exchange" in l
                                        for l in permutes),
            "local": sum("repro.dist.local" in n for n in names),
            "both": sum("repro.dist.exchange" in n
                        and "repro.dist.local" in n for n in names)}))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["permutes"] >= 4                   # two axes, both ways
    assert out["permutes_in_exchange"] == out["permutes"]
    assert out["local"] > 0 and out["both"] == 0
