"""Each cell's set-up, window, trace and check, rehearsed at a tiny size on
the CPU, and the command's refusal to report anything off the chip."""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import harness  # noqa: E402

ONE_CHIP = ("triad.hbm", "jacobi2d.hbm", "triad.small")
PEAKS = {"hbm_GBps": 819}  # the CPU has no row in peaks.json


def _run(root, workload, trace):
    return harness.run_cell(workload, 2**33 + 7, 0.3, trace, root=root,
                            require_tpu=False, peaks=PEAKS)


def _cpu_env(**extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **extra)
    return env


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_one_chip_cell_runs_and_is_correct(tiny_root, workload):
    bench = harness.load_json(tiny_root / "BENCHMARK.json")
    r = _run(tiny_root, workload, False)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert set(r["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, workload, "end_to_end")}
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["platform"] == "cpu" and r["device"]["count"] >= 1
    assert list(r["check"])[-1] == "compiles_in_window"
    assert r["check"]["compiles_in_window"]["value"] == 0
    assert list(r)[-1] == "check"

    t = _run(tiny_root, workload, True)
    assert t["correct"]
    assert set(t["metrics"]) == {
        m["name"] for m in harness.metrics_for(bench, workload, "per_layer")}
    assert 0 < t["device"]["busy_s"] <= t["device"]["window_s"]
    assert 0 < t["metrics"]["emitted_kernel_roofline"]["value"]
    assert t["breakdown"]["device_ops"] and t["breakdown"]["idle_gaps"]


COPY_REF = '''
import jax.numpy as jnp
from jax import lax


def reference(x, n, cfg, rnd):
    a, b = rnd(x["A"]), rnd(x["B"])
    inside = lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1) < n
    return {"A": (jnp.where(inside, b, a), jnp.where(inside, jnp.abs(b), 0.0)),
            "B": (b, None)}


def traffic_bytes(cfg, n):
    return {"hbm": 2 * 4 * cfg["driver_config"]["programs"] * n}
'''

COUNT_READER = '''
def read(ctx):
    return float(max(len(v) for v in ctx.trace.runs.values())) or None
'''


def test_new_cell_config_and_metric_come_from_new_files(tiny_root):
    """A cell, a configuration, a traffic mix and a per-layer metric that
    a later change adds as files of their own run with no file edited."""
    pb = tiny_root / "perfbench"
    before = {p: p.read_bytes() for p in pb.rglob("*") if p.is_file()}
    (pb / "configs" / "stream_copy.json").write_text(json.dumps({
        "name": "stream_copy", "entry": "driver", "pattern": "stream_copy",
        "driver_config": {"template": "independent", "programs": 2,
                          "ntimes": 1, "parametric": "auto"},
        "limits": {"max_rel_err": 1e-6}}))
    (pb / "configs" / "stream_copy.py").write_text(COPY_REF)
    (pb / "traffic" / "copy_ladder.json").write_text(
        json.dumps({"n": [8192, 16384], "reps": 2}))
    (pb / "metrics" / "executions.mem.py").write_text(COUNT_READER)
    bench = harness.load_json(tiny_root / "BENCHMARK.json")
    bench["configs"].append({"name": "stream_copy", "source": "STREAM copy",
                             "file": "perfbench/configs/stream_copy.json",
                             "reduced": [], "why": "copy"})
    bench["workloads"].append({"name": "copy.tiny", "config": "stream_copy",
                               "traffic": "copy_ladder", "chips": 1,
                               "why": "copy"})
    for m in bench["end_to_end"]:
        if "workloads" in m and "triad.hbm" in m["workloads"]:
            m["workloads"].append("copy.tiny")
    bench["per_layer"].append({"name": "executions.mem", "unit": "1",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "mem_GBps",
                               "workloads": ["copy.tiny"]})
    (tiny_root / "BENCHMARK.json").write_text(json.dumps(bench))

    r = _run(tiny_root, "copy.tiny", False)
    assert r["correct"] and {"mem_GBps", "pass_ms_p95", "setup_s"} <= set(
        r["metrics"])
    t = _run(tiny_root, "copy.tiny", True)
    assert t["correct"] and t["metrics"]["executions.mem"]["value"] >= 2
    assert all(p.read_bytes() == b for p, b in before.items())


def test_command_refuses_without_a_chip(tiny_root):
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "triad.small",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tiny_root, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no TPU found" in p.stderr


def test_command_refuses_in_a_bare_checkout(tmp_path):
    shutil.copytree(REPO / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "triad.hbm",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_cpu_env(), capture_output=True, text=True,
        timeout=120)
    assert p.returncode != 0 and p.stdout == ""
    assert "no program under test" in p.stderr
