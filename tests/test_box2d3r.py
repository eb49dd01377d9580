"""Box-2D3R (Box-2D49P) f32, the benchmark's high-radius box deployment
(``bench/configs/box2d3r-f32.json``), through the normal path on the CPU:
the auto plan for a v5e takes the unfused VPU kernel ``direct`` by the
paper's Eq. 16, and one call of it matches both plain oracles -- the
program's ``repro.stencil.reference`` and the benchmark's own copy --
tightly enough that the same step computed in bfloat16 fails."""
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import perfmodel as pm
from repro.kernels import stencil_plan
from repro.stencil import StencilSpec, make_weights, reference

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench import oracle  # noqa: E402

CONFIG = os.path.join(ROOT, "bench", "configs", "box2d3r-f32.json")
GRID = (64, 256)

#: Relative limit, max |y - oracle| over max |oracle|.  The kernel sums
#: the 49 f32 products in the oracle's row-major offset order, so only
#: where a compiler fuses a multiply and an add into one rounding can the
#: two part: a few f32 ulp (1.2e-7; the CPU reads 2e-7), while the same step
#: in bfloat16 (8-bit mantissa) reads near 2e-2.
REL_LIMIT = 1e-6


@pytest.fixture(scope="module")
def config():
    with open(CONFIG) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def weights(config):
    spec = StencilSpec(config["shape"], config["dim"], config["radius"])
    return make_weights(spec, seed=config["weights"]["seed"],
                        normalize=config["weights"]["normalize"])


@pytest.fixture(scope="module")
def plan(config, weights):
    return stencil_plan(weights, GRID, np.dtype(config["dtype"]),
                        config["t"], hw=pm.TPU_V5E_BF16,
                        boundary=config["boundary"])


@pytest.fixture(scope="module")
def x():
    return jnp.asarray(np.random.default_rng(2**31 + 15).normal(
        size=GRID).astype(np.float32))


def _rel(y, ref):
    return float(jnp.max(jnp.abs(y - ref)) / jnp.max(jnp.abs(ref)))


def test_config_is_the_published_deployment(config, weights):
    assert (config["shape"], config["dim"], config["radius"]) == \
        ("box", 2, 3)
    assert config["grid"] == [10240, 10240] and config["t"] == 1
    assert config["reduced"] == []
    assert weights.shape == (7, 7) and np.count_nonzero(weights) == 49
    assert float(weights.sum()) == pytest.approx(1.0, rel=1e-6)


def test_auto_plan_takes_direct_by_eq16(plan):
    assert plan.backend == "direct"
    assert "Eq. 16" in plan.decision.reason
    cands = plan.decision.candidates
    assert cands["direct"] > cands["matmul"]


def test_one_call_matches_both_oracles(plan, config, weights, x):
    y = plan(x)
    modes = (config["boundary"],) * config["dim"]
    w = jnp.asarray(weights)
    ref = reference.apply_stencil(x, w, config["boundary"])
    ours = oracle.apply_stencil(x, w, modes)
    np.testing.assert_array_equal(np.asarray(ours), np.asarray(ref))
    assert _rel(y, ref) <= REL_LIMIT


def test_bfloat16_step_fails_the_limit(config, weights, x):
    ref = reference.apply_stencil(x, jnp.asarray(weights),
                                  config["boundary"])
    low = reference.apply_stencil(
        x.astype(jnp.bfloat16), jnp.asarray(weights, jnp.bfloat16),
        config["boundary"]).astype(jnp.float32)
    assert _rel(low, ref) > 100 * REL_LIMIT
