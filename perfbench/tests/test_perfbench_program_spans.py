"""The benchmark's trace reduction on a trace that holds the program's
own spans (``repro.*``), recorded on the CPU through the harness's
window loop: one dispatch span per timed call lands in the trace, and
the accepted per-layer readers read the same with and without them."""
from __future__ import annotations

import pathlib
import sys
from types import SimpleNamespace

REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (REPO, REPO / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from perfbench import harness  # noqa: E402
from perfbench import trace as tr  # noqa: E402

READERS = ("emitted_kernel_roofline", "launch_gap_us.mem", "idle_share.mem",
           "stage_s")
LADDER = [8192, 16384]
REPS = 2


def _without_program_spans(pd):
    """``pd`` as ``from_profile`` sees it, less every ``repro.`` event."""
    return SimpleNamespace(planes=[
        SimpleNamespace(name=plane.name, lines=[
            SimpleNamespace(name=line.name, events=[
                ev for ev in line.events if not ev.name.startswith("repro.")])
            for line in plane.lines])
        for plane in pd.planes])


def _traced_window(tmp_path):
    import jax
    import jax.numpy as jnp

    import repro.core as core
    from repro.core import spans

    cfg = harness.load_json(REPO / "perfbench" / "configs" /
                            "stream_triad.json")
    ref = harness.load_module(REPO / "perfbench" / "configs" /
                              "stream_triad.py")
    driver = core.Driver(lambda env: core.triad(scalar=3.0),
                         core.DriverConfig(**cfg["driver_config"]),
                         cache=core.TranslationCache())
    with spans.recording() as got:
        preps = driver.prepare(LADDER, parallel=False)
        calls = []
        for p in preps:
            arrays = p.lowered.pattern.allocate(p.lowered.env)
            tup = tuple(jnp.asarray(arrays[k]) for k in p.compiled.names)
            fn = p.executable()
            n = int(p.env["n"])
            calls.append(harness.Call(
                label=f"n={n}", run=lambda fn=fn, tup=tup: fn(tup),
                keep=lambda out: None, nbytes=ref.traffic_bytes(cfg, n)))
        harness.warm_up(calls)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            win = harness.run_passes(calls, REPS, 0.0, spans=True)
        finally:
            jax.profiler.stop_trace()
    stage_s = (sum({id(p.lowered): p.lowered.lower_seconds
                    for p in preps}.values())
               + sum({id(p.compiled.executable): p.compiled.compile_seconds
                      for p in preps}.values()))
    return win, stage_s, got


def test_program_spans_leave_the_accepted_readers_unchanged(tmp_path):
    from jax.profiler import ProfileData

    win, stage_s, got = _traced_window(tmp_path)
    files = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    dispatch = [ev for plane in pd.planes for line in plane.lines
                for ev in line.events if ev.name == "repro.dispatch"]
    # one dispatch span per timed call in the trace, and in the buffer
    # one more per rung for the warm-up call
    assert len(dispatch) == win.calls == len(LADDER) * REPS * len(win.passes)
    assert len([r for r in got if r[2] == "repro.dispatch"]) == \
        win.calls + len(LADDER)
    assert [r[2] for r in got if r[1] is None][0] == "repro.prepare"

    peaks = harness.load_peaks(REPO, "TPU v5 lite")
    values = []
    for profile in (pd, _without_program_spans(pd)):
        trace = tr.from_profile(profile, span_prefix=harness.SPAN)
        assert trace.ops and trace.runs and trace.host
        ctx = harness.Context(setup_s=1.0, stage_s=stage_s, window=win,
                              trace=trace, peaks=peaks)
        values.append({
            name: harness.load_module(
                REPO / "perfbench" / "metrics" / f"{name}.py").read(ctx)
            for name in READERS})
    assert values[0] == values[1]
    assert all(v is not None for v in values[0].values())
