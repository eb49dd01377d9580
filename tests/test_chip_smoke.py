"""chip_smoke.py on the CPU: its phase functions at tiny sizes in interpret
mode (the chip run's control flow and checks, without the chip), its
four-chip phase on four faked CPU devices, and its refusal to run
anywhere but a TPU."""
import json
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _ok(rows, n):
    assert len(rows) == n
    for row in rows:
        assert row["interpret"] is True
        assert row["backend"] != "reference"
        assert row["max_err"] <= row["tol"]
    return rows


def test_phase_2d_tiny():
    rows = _ok(chip_smoke.phase_2d(n=64, t=2, n_steps=2, interpret=True), 3)
    assert [r["requested"] for r in rows] == \
        ["auto", "fused_direct", "fused_matmul_reuse"]
    assert [r["column_shifts"] for r in rows] == ["roll", "roll", "-"]


def test_phase_substrates_tiny():
    (row,) = _ok(chip_smoke.phase_substrates(n=64, t=2, interpret=True), 1)
    assert row["bitwise"]


def test_phase_3d_tiny():
    (row,) = _ok(chip_smoke.phase_3d(n=16, interpret=True), 1)
    assert row["column_shifts"] == "slice"


def test_phase_boundary_tiny():
    rows = _ok(chip_smoke.phase_2d(n=64, t=2, n_steps=2, interpret=True,
                                   boundary=("reflect", "periodic")), 3)
    assert all(r["boundary"] == ("reflect", "periodic") for r in rows)


def test_phase_serving_tiny():
    (row,) = _ok(chip_smoke.phase_serving(n=32, requests=16, window=8,
                                          interpret=True), 1)
    assert row["responded"] == 16


def test_smoke_check_rejects_interpret_mode_on_the_chip_path():
    """The chip path (interpret=None) must resolve compiled plans; on
    the CPU the default resolves interpret mode, which the check refuses."""
    with pytest.raises(chip_smoke.SmokeFailure, match="interpret=True"):
        chip_smoke.phase_3d(n=16)


def test_phase_four_chips_on_fake_devices():
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT, env.get("PYTHONPATH", "")])
    code = textwrap.dedent("""
        import json, chip_smoke
        rows = chip_smoke.phase_four_chips(n=64, t=2, interpret=True)
        print(json.dumps([[r["mode"], r["max_err"]] for r in rows]))
    """)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=560)
    assert r.returncode == 0, r.stderr
    rows = json.loads(r.stdout.strip().splitlines()[-1])
    assert [m for m, _ in rows] == ["fused", "stepwise", "overlap"]
    assert all(err <= chip_smoke.F32_TOL for _, err in rows)


def test_main_refuses_the_cpu(monkeypatch, tmp_path, capsys):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main([]) == 1
    out = capsys.readouterr()
    assert "needs a TPU" in out.err
    assert '"ok"' not in out.out


def test_script_alone_fails(tmp_path):
    """Copied without the repository, the script exits nonzero and prints
    no result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, env=env, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
