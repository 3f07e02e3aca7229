"""``chip_smoke.py``'s phases at a tiny size on the CPU, and its refusal
to run anywhere but on a TPU."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_xla_tiny(smoke, capsys):
    rows = smoke.phase_xla((4096, 16384, 65536), 66, ntimes=2, reps=1)
    triad = [r for r in rows if r["phase"] == "a.triad"]
    assert [r["rung"] for r in triad] == [4096, 16384, 65536]
    assert all(r["compile_misses"] == 1 for r in triad)
    assert all(r["param_path"] == "strided" for r in triad)
    grid, = [r for r in rows if r["phase"] == "a.jacobi2d"]
    assert grid["rung"] == 66 and grid["oracle"] == "validated+bitexact"
    assert all(r["gbs"] > 0 and r["device"]["platform"] == "cpu"
               for r in rows)
    printed = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln)["phase"] for ln in printed] == \
        [r["phase"] for r in rows]


def test_phase_pallas_tiny(smoke):
    rows = smoke.phase_pallas(4096, 34, ntimes=2, reps=1, mode="interpret")
    assert [r["phase"] for r in rows] == [
        "b.triad", "b.jacobi2d", "b.triad_ladder", "b.triad_ladder",
        "b.over_vmem"]
    assert all(r["pallas_mode"] == "interpret" for r in rows[:4])
    assert [r["rung"] for r in rows[2:4]] == [1024, 4096]
    assert rows[3]["param_path"] == "strided"
    assert rows[4]["vmem_bytes"] == 3 * (1 << 24) * 4


def test_phase_pallas_rejects_wrong_mode(smoke):
    with pytest.raises(AssertionError, match="pallas_mode"):
        smoke.phase_pallas(4096, 34, ntimes=2, reps=1, mode="compiled")


def test_phase_kernels_tiny(smoke):
    rows = smoke.phase_kernels(8192, 18, block=(8, 16), reps=1)
    assert [r["phase"] for r in rows] == [
        "c.triad", "c.triad_interleaved", "c.jacobi3d_streaming"]
    assert all(r["gbs"] > 0 for r in rows)


def _run(args, cwd, env_extra=None, timeout=300):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(env_extra or {})
    return subprocess.run([sys.executable, *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_phase_collectives_on_forced_cpu_mesh():
    code = (
        "import json, sys\n"
        f"sys.path.insert(0, {str(ROOT)!r})\n"
        f"sys.path.insert(0, {str(ROOT / 'src')!r})\n"
        "import chip_smoke\n"
        "rows = chip_smoke.phase_collectives((256, 4096), devices=4, reps=1)\n"
        "print(json.dumps(rows))\n"
    )
    out = _run(["-c", code], ROOT, {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    assert len(rows) == 4 and all(r["devices"] == 4 for r in rows)
    assert all(r["values_ok"] and abs(r["agreement"] - 1) <= 0.10
               for r in rows)


def test_main_refuses_without_tpu():
    out = _run(["chip_smoke.py"], ROOT, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert "no TPU found" in out.stderr
    assert '"ok"' not in out.stdout


def test_main_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = _run(["chip_smoke.py"], tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout == ""
