"""Where entry points keep JAX's persistent compilation cache
(``repro.core.envutil.init_compile_cache``): in ``JAX_COMPILATION_CACHE_DIR``
when it is set, and nowhere else; otherwise in the checkout's fixed
``.jax_cache``."""
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import envutil

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

CHILD = textwrap.dedent("""
    import sys
    import jax, jax.numpy as jnp
    from repro.core import envutil
    envutil.CHECKOUT_CACHE_DIR = sys.argv[1]
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    print(envutil.init_compile_cache())
    jax.jit(lambda x: jnp.sin(x) * 2 + 1)(jnp.ones(64)).block_until_ready()
""")


def _run(checkout_dir, placed=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    if placed is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(placed)
    r = subprocess.run([sys.executable, "-c", CHILD, str(checkout_dir)],
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode == 0, r.stderr
    return r.stdout.strip().splitlines()[-1]


def _entries(path):
    return os.listdir(path) if os.path.isdir(path) else []


def test_placed_dir_is_used_and_nothing_else(tmp_path):
    placed, checkout = tmp_path / "placed", tmp_path / "checkout"
    assert _run(checkout, placed) == str(placed)
    assert _entries(placed)
    assert not _entries(checkout)


def test_unplaced_cache_goes_to_the_checkout(tmp_path):
    checkout = tmp_path / "checkout"
    assert _run(checkout) == os.path.normpath(checkout)
    assert _entries(checkout)


def test_checkout_dir_is_fixed_at_the_repo_root():
    root = os.path.normpath(os.path.join(os.path.dirname(__file__), ".."))
    assert os.path.normpath(envutil.CHECKOUT_CACHE_DIR) == \
        os.path.join(root, ".jax_cache")


@pytest.mark.parametrize("kind,ok", [("TPU v5 lite", True), ("cpu", False),
                                     ("TPU v4", False)])
def test_hardware_for_is_keyed_by_device_kind(kind, ok):
    from types import SimpleNamespace
    from repro.core.perfmodel import TPU_V5E_BF16, hardware_for

    device = SimpleNamespace(device_kind=kind)
    if ok:
        assert hardware_for(device) is TPU_V5E_BF16
    else:
        with pytest.raises(ValueError, match="no hardware spec"):
            hardware_for(device)
