"""Program spans (``repro.core.spans``): off by default and free on the
hot path, nested at the staging boundaries, one per dispatched call, on
the profiler's clock; and the emitters' named scopes."""
from __future__ import annotations

import contextlib
import pathlib
import re
import time

import jax
import jax.numpy as jnp
import pytest

from repro import suite
from repro.core import Driver, DriverConfig, TranslationCache, spans, triad
from repro.core.staging import ParamCompiled
from repro.suite import SweepPlan, VariantSpec, config_axis, env_axis

LADDER = [8192, 16384, 24576]
TRIAD = dict(template="independent", programs=4, ntimes=1,
             parametric="auto", param_path="auto", reps=1)


def _driver(**kw) -> Driver:
    return Driver(lambda env: triad(scalar=3.0),
                  DriverConfig(**{**TRIAD, **kw}), cache=TranslationCache())


def _inputs(p):
    arrays = p.lowered.pattern.allocate(p.lowered.env)
    return tuple(jnp.asarray(arrays[k]) for k in p.compiled.names)


def _named(records, name):
    return [r for r in records if r[2] == name]


def _seconds(rec) -> float:
    return (rec[4] - rec[3]) / 1e9


@pytest.fixture(autouse=True)
def _spans_off():
    spans.disable()
    spans.drain()
    yield
    spans.disable()
    spans.drain()


def test_off_by_default_and_still_timed():
    assert not spans._on
    with spans.span("repro.test", k=1) as sp:
        time.sleep(0.001)
    assert sp.seconds >= 0.001 and sp.end - sp.start == sp.seconds
    assert spans.drain() == []


def test_off_bind_returns_the_plain_callable_and_records_nothing():
    preps = _driver().prepare(LADDER, parallel=False)
    p = preps[0]
    assert isinstance(p.compiled, ParamCompiled)
    fn = p.executable()
    # the threading wrapper closes over the executable's own run
    cells = [c.cell_contents for c in fn.__closure__]
    assert any(c is p.compiled.run for c in cells)
    assert fn.__qualname__.endswith("bind.<locals>.fn")
    spec = _driver(parametric=False).prepare([LADDER[0]], parallel=False)[0]
    assert spec.compiled.donated
    assert spec.executable().__qualname__.endswith("bind.<locals>.fn")
    # decided at bind: spans turned on afterwards do not reach the loop
    spans.enable()
    tup = _inputs(p)
    for _ in range(3):
        tup = fn(tup)
    jax.block_until_ready(tup)
    assert _named(spans.drain(), "repro.dispatch") == []


def test_undonated_bind_is_the_run_itself_when_off():
    c = _driver(parametric=False, donate=False).prepare(
        [LADDER[0]], parallel=False)[0].compiled
    assert not c.donated
    assert c.bind() is c.run


def test_on_nests_staging_under_prepare_and_spans_each_dispatch():
    d = _driver()
    with spans.recording() as got:
        preps = d.prepare(LADDER, parallel=False)
        fns = [p.executable() for p in preps]
        calls = 0
        for p, fn in zip(preps, fns):
            tup = _inputs(p)
            for _ in range(2):
                tup = fn(tup)
                calls += 1
            jax.block_until_ready(tup)
    (prep,) = _named(got, "repro.prepare")
    assert prep[1] is None
    assert prep[5] == {"points": 3, "path": "parametric"}
    (resolve,) = _named(got, "repro.resolve")
    lowers, compiles = _named(got, "repro.lower"), _named(got, "repro.compile")
    assert resolve[1] == prep[0]
    assert len(lowers) == len(compiles) == len(LADDER)
    assert all(r[1] == prep[0] for r in lowers + compiles)
    assert [r[5]["cache"] for r in lowers] == ["built", "hit", "hit"]
    assert compiles[0][5]["source"] in ("compiled", "disk")
    assert [r[5]["source"] for r in compiles[1:]] == ["memory", "memory"]
    for r in lowers + compiles:   # children lie inside their parent
        assert prep[3] <= r[3] <= r[4] <= prep[4]
    dispatch = _named(got, "repro.dispatch")
    assert len(dispatch) == calls
    assert [r[5]["n"] for r in dispatch] == [n for n in LADDER for _ in "ab"]


def test_stage_seconds_are_the_spans_durations():
    d = _driver()
    with spans.recording() as got:
        p = d.prepare(LADDER, parallel=False)[0]
    built_lower = [r for r in _named(got, "repro.lower")
                   if r[5]["cache"] == "built"]
    built_compile = [r for r in _named(got, "repro.compile")
                     if r[5]["source"] != "memory"]
    assert len(built_lower) == len(built_compile) == 1
    assert p.lowered.lower_seconds == pytest.approx(
        _seconds(built_lower[0]), abs=2e-9)
    assert p.compiled.compile_seconds == pytest.approx(
        _seconds(built_compile[0]), abs=2e-9)
    assert p.compiled.compile_seconds > 0


def test_compile_spans_in_worker_threads_nest_under_prepare():
    d = _driver(parametric=False)
    with spans.recording() as got:
        preps = d.prepare([256 * 4, 512 * 4], parallel=True)
    (prep,) = _named(got, "repro.prepare")
    assert prep[5]["path"] == "specialized"
    compiles = _named(got, "repro.compile")
    assert len(compiles) == 2 and all(r[1] == prep[0] for r in compiles)
    assert sorted(p.compiled.compile_seconds for p in preps) == \
        pytest.approx(sorted(_seconds(r) for r in compiles), abs=2e-9)


def test_measure_and_validate_spans():
    d = _driver(parametric=False, validate_n=64)
    with spans.recording() as got:
        p = d.prepare([1024], parallel=False)[0]
        d.measure_point(p)
        d.validate({"n": 64})
    assert len(_named(got, "repro.measure")) == 1
    (val,) = _named(got, "repro.validate")
    assert val[1] is None
    assert all(r[1] == val[0] for r in _named(got, "repro.lower")[1:])


def test_buffer_bound_counts_drops(monkeypatch):
    monkeypatch.setattr(spans, "CAPACITY", 3)
    with spans.recording() as got:
        for _ in range(5):
            with spans.span("repro.test"):
                pass
        assert spans.dropped() == 2
    assert len(got) == 3 and spans.dropped() == 0


def test_recording_restores_the_previous_state():
    def records_a_span():
        with spans.span("repro.test"):
            pass
        return len(spans.drain()) == 1

    with spans.recording() as got:
        with spans.span("repro.test"):
            pass
    assert len(got) == 1 and not records_a_span()
    spans.enable()
    with spans.recording():
        pass
    assert records_a_span()


def test_engine_executor_reads_the_group_spans():
    plan = SweepPlan.product(config_axis("programs", (1, 2)),
                             env_axis((256, 512)))
    cfg = DriverConfig(template="unified", ntimes=2, reps=1)
    for backend in (suite.SerialBackend(), suite.ThreadPoolBackend(2)):
        with spans.recording() as got:
            report = suite.run_plan(
                lambda env: triad(), [VariantSpec("t", cfg)], plan,
                quick=True, cache=TranslationCache(), backend=backend)
        ex = report.executor
        stage = _named(got, "repro.group.stage")
        measure = _named(got, "repro.group.measure")
        assert len(measure) == ex["groups"]
        assert ex["stage_seconds"] == pytest.approx(
            sum(_seconds(r) for r in stage), abs=1e-8)
        assert ex["measure_seconds"] == pytest.approx(
            sum(_seconds(r) for r in measure), abs=1e-8)


def test_spans_share_the_profilers_clock(tmp_path):
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        time.sleep(0.02)
        with spans.recording() as got:
            with spans.span("repro.clock"):
                time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
    (rec,) = got
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    pd = ProfileData.from_file(str(path))
    start = None
    events = []
    for plane in pd.planes:
        stats = dict(plane.stats)
        if "profile_start_time" in stats:
            start = int(stats["profile_start_time"])
        for line in plane.lines:
            events += [ev for ev in line.events if ev.name == "repro.clock"]
    assert start is not None and len(events) == 1
    # the trace keeps the name as given: attributes ride in the buffer
    assert abs(start + int(events[0].start_ns) - rec[3]) < 1_000_000
    assert abs(int(events[0].duration_ns) - (rec[4] - rec[3])) < 1_000_000


def _strided():
    p = _driver().prepare(LADDER, parallel=False)[0]
    assert p.compiled.param_path == "strided"
    return p.compiled.executable.as_text()


def test_strided_executable_carries_the_emitter_scopes():
    text = _strided()
    for scope in ("repro.window.read", "repro.window.combine",
                  "repro.window.write"):
        assert scope in text, scope


def test_named_scopes_leave_the_ops_unchanged(monkeypatch):
    def ops(text):
        text = re.sub(r",? metadata=\{[^}]*\}", "", text)
        return [ln.strip() for ln in text.splitlines() if ln.strip()]

    scoped = ops(_strided())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _strided()
    assert "repro.window" not in plain
    assert ops(plain) == scoped
