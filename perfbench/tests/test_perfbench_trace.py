"""The trace reduction, on intervals counted by hand and on a small trace
recorded on the CPU."""
from __future__ import annotations

import pathlib
import sys

REPO = pathlib.Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

from perfbench import trace as tr  # noqa: E402


def test_union_merges_overlaps_and_nesting():
    got = tr.union([(0, 10, "a"), (5, 20, "b"), (30, 40, "c"), (32, 35, "d")])
    assert got == [(0, 20), (30, 40)]


def test_self_time_takes_nested_ops_out_of_their_loop():
    ops = [(0, 100, "while"), (10, 30, "fusion"), (30, 60, "dus"),
           (60, 70, "fusion"), (200, 250, "copy")]
    got = {k: round(v * 1e9) for k, v in tr.self_times(ops).items()}
    assert got == {"while": 40, "fusion": 30, "dus": 30, "copy": 50}


def test_busy_runs_gaps_and_idle_attribution():
    t = tr.Trace(
        ops={"/device:TPU:0": [(100, 200, "f"), (300, 350, "f")],
             "/device:TPU:1": [(100, 150, "f"), (300, 400, "f")]},
        runs={"/device:TPU:0": [(100, 200, "m"), (300, 350, "m")],
              "/device:TPU:1": [(100, 150, "m"), (300, 400, "m")]},
        host=[(0, 500, "pass"), (90, 120, "call:x"), (120, 240, "wait:x")])
    assert tr.busy_s(t) == 150e-9          # (150 + 150) / 2 devices
    assert tr.run_s(t) == 150e-9
    assert sorted(tr.launch_gaps_s(t)) == [100e-9, 150e-9]
    gaps = tr.idle_gaps(t)
    # device 0's gap 200..300 has its middle in "pass" only; device 1's
    # gap 150..300 has its middle (225) inside "wait:x"
    assert gaps == {"pass": 50e-9, "wait:x": 75e-9}
    b = tr.breakdown(t)
    assert b["device_ops"] == [["f", 150e-9]]
    assert [k for k, _ in b["idle_gaps"]] == ["wait:x", "pass"]


def test_short_name_of_a_tpu_op_event():
    assert tr.short_name("%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p)") == \
        "fusion.3"
    assert tr.short_name("psum.7") == "psum.7"


def test_reduces_a_trace_recorded_on_the_cpu(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda a, b: a + 3.0 * b)
    a = jnp.ones((1 << 16,), jnp.float32)
    jax.block_until_ready(f(a, a))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        for _ in range(3):
            with jax.profiler.TraceAnnotation("pb.call"):
                out = f(a, a)
            with jax.profiler.TraceAnnotation("pb.wait"):
                jax.block_until_ready(out)
    finally:
        jax.profiler.stop_trace()
    t = tr.load(tmp_path, span_prefix="pb.")
    assert len(t.ops) == 1 and tr.busy_s(t) > 0
    assert max(len(v) for v in t.runs.values()) == 3
    assert len(tr.launch_gaps_s(t)) == 2
    assert {name for _, _, name in t.host} == {"call", "wait"}
    b = tr.breakdown(t)
    assert b["device_ops"] and len(b["device_ops"]) <= tr.TOP
    assert all(sec > 0 for _, sec in b["device_ops"])
