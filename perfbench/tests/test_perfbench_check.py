"""The comparison that decides ``correct``: sound runs pass, and the
control and each fault the cells can have fail it. Tiny sizes on the
CPU; the all-reduce on four virtual devices in a process of its own."""
from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import calibrate, harness  # noqa: E402

PEAKS = {"hbm_GBps": 819}


def _run(root, workload):
    return harness.run_cell(workload, 12345, 0.2, False, root=root,
                            require_tpu=False, peaks=PEAKS)


@pytest.mark.parametrize("workload", ("triad.hbm", "jacobi2d.hbm"))
def test_control_fails_where_the_program_passes(tiny_root, workload):
    got = calibrate.readings(workload, [101, 102], [201, 202], 0.1,
                             root=tiny_root, require_tpu=False)
    cfg_limit = 1e-6
    assert got["program"]["max_rel_err"] <= cfg_limit
    assert got["program"]["exact_mismatches"] == 0
    # bfloat16 keeps 8 bits: errors of 2^-9 and more, every input rounded
    assert got["control"]["max_rel_err"] > 1e-3
    assert got["control"]["exact_mismatches"] > 0


def _unchanged(real, prep):
    """The executable hands its state back unchanged."""
    return lambda tup: tup


def _half_the_rung(real, prep):
    """The executable runs over half of each rung and leaves the rest."""
    if prep.parametric:
        return prep.compiled.bind({**prep.env, "n": prep.env["n"] // 2})
    run = prep.compiled.bind()

    def fn(tup):
        out = run(tup)
        a = out[0]
        half = a.shape[1] // 2
        return (a.at[:, half:].set(0.0),) + tuple(out[1:])
    return fn


def _altered(real, prep):
    """One element of the answer changed where it is produced."""
    run = real(prep)

    def fn(tup):
        out = run(tup)
        a = out[0]
        mid = tuple(s // 2 for s in a.shape)
        return (a.at[mid].add(1.0),) + tuple(out[1:])
    return fn


@pytest.mark.parametrize("fault", (_unchanged, _half_the_rung, _altered))
@pytest.mark.parametrize("workload", ("triad.hbm", "jacobi2d.hbm"))
def test_a_broken_timed_path_is_not_correct(tiny_root, monkeypatch, workload,
                                            fault):
    from repro.core.drivers import Prepared

    real = Prepared.executable
    monkeypatch.setattr(Prepared, "executable",
                        lambda self: fault(real, self))
    r = _run(tiny_root, workload)
    assert r["correct"] is False and r["failed"] > 0
    assert (r["check"]["max_rel_err"]["value"] > 1e-6
            or r["check"]["exact_mismatches"]["value"] > 0)


FOUR_DEVICES = textwrap.dedent('''
    import json, sys
    sys.path.insert(0, sys.argv[2])
    from perfbench import calibrate, harness
    root = sys.argv[1]
    out = {}
    r = harness.run_cell("allreduce.4chip", 2**32 + 3, 0.3, False, root=root,
                         require_tpu=False)
    out["run"] = r
    t = harness.run_cell("allreduce.4chip", 77, 0.3, True, root=root,
                         require_tpu=False, peaks={})
    out["trace"] = t
    out["readings"] = calibrate.readings("allreduce.4chip", [5, 6], [7, 8],
                                         0.1, root=root, require_tpu=False)

    import repro.suite.collectives as coll
    real = coll._sharded_ops

    def no_exchange(mesh):
        import jax
        from jax.sharding import PartitionSpec as P
        ops = dict(real(mesh))
        ops["all_reduce"] = jax.jit(jax.shard_map(
            lambda x: x, mesh=mesh, in_specs=P("device"), out_specs=P(None),
            check_vma=False))
        return ops

    coll._sharded_ops = no_exchange
    out["fault"] = harness.run_cell("allreduce.4chip", 9, 0.3, False,
                                    root=root, require_tpu=False)
    print(json.dumps(out, default=str))
''')


def test_allreduce_on_four_virtual_devices(tiny_root):
    env = {k: v for k, v in os.environ.items()}
    env.update(JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", FOUR_DEVICES, str(tiny_root),
                        str(REPO)], env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    run, tr = out["run"], out["trace"]
    assert run["correct"] and run["device"]["count"] == 4
    assert set(run["metrics"]) == {"ici_GBps", "pass_ms_p95", "setup_s"}
    assert tr["correct"] and set(tr["metrics"]) == {"idle_share.ici",
                                                    "stage_s"}
    assert out["readings"]["program"]["max_rel_err"] <= 1e-6
    assert out["readings"]["control"]["max_rel_err"] > 1e-3
    fault = out["fault"]
    assert fault["correct"] is False and fault["failed"] > 0
