"""Resilience-layer tests (the PR-6 acceptance contract).

Covers: the failure taxonomy (stages, transient flags, classification,
FailureRecord schema + JSON round-trip), adaptive time_fn (CV mode, rep
budget, straggler counting, wall-clock watchdog raising BudgetExceeded),
time_pair's strict A/B alternation, the capacity pre-flight
(CapacityRefused instead of OOM), fault-isolated run_plan (injected
lower/compile/validate/measure faults, per-point isolation in
multi-group plans, demotion-ladder order, transient retry), the
resumable run journal (write → crash → resume with byte-identical
replayed rows and zero recompiles), and the RunReport schema.
"""
from __future__ import annotations

import dataclasses
import json
import time

import pytest

from repro.core import (
    BenchFailure,
    BudgetExceeded,
    CapacityRefused,
    CompileFailure,
    Driver,
    DriverConfig,
    FailureRecord,
    LowerFailure,
    MeasureFailure,
    ResiliencePolicy,
    SweepFailures,
    TranslationCache,
    ValidateFailure,
    classify_failure,
    gather,
    time_fn,
    time_pair,
    triad,
)
from repro.core import drivers as drivers_mod
from repro.core.staging import ParamLowered
from repro.suite import (
    RunJournal,
    SweepPlan,
    VariantSpec,
    env_axis,
    pattern_axis,
    run_plan,
    stable_fingerprint,
)

CFG = DriverConfig(template="unified", programs=2, ntimes=2, reps=1,
                   validate_n=None)


def _plan(*ns):
    return SweepPlan.product(env_axis(tuple(ns)))


# ---------------------------------------------------------------------------
# taxonomy
# ---------------------------------------------------------------------------


def test_taxonomy_stages_and_transience():
    assert LowerFailure.stage == "lower" and not LowerFailure.transient
    assert CompileFailure.stage == "compile" and not CompileFailure.transient
    assert ValidateFailure.stage == "validate"
    assert MeasureFailure.stage == "measure" and MeasureFailure.transient
    assert issubclass(BudgetExceeded, MeasureFailure)
    assert BudgetExceeded.transient
    assert CapacityRefused.stage == "capacity"
    assert not CapacityRefused.transient
    for cls in (LowerFailure, CompileFailure, ValidateFailure,
                MeasureFailure, BudgetExceeded, CapacityRefused):
        assert issubclass(cls, BenchFailure)
        assert issubclass(cls, RuntimeError)


def test_classify_wraps_and_passes_through():
    plain = ValueError("boom")
    wrapped = classify_failure(plain, "compile", template="unified")
    assert isinstance(wrapped, CompileFailure)
    assert wrapped.cause is plain
    assert wrapped.context["template"] == "unified"
    assert "ValueError" in str(wrapped)
    # an existing BenchFailure keeps its own stage; context merges
    cap = CapacityRefused("too big", context={"budget_bytes": 10})
    again = classify_failure(cap, "measure", env={"n": 4})
    assert again is cap
    assert again.stage == "capacity"
    assert again.context["env"] == {"n": 4}
    assert again.context["budget_bytes"] == 10  # original context wins


def test_failure_record_json_roundtrip():
    fr = FailureRecord(
        variant="v", label="n256", stage="compile", error="CompileFailure",
        message="boom", pattern="triad", template="unified",
        schedule="identity", backend="jax", env={"n": 256},
        axis_point={"n": "n256"}, context={"cause": "ValueError",
                                           "weird": object()},
        attempts=3, demotions=("strided->gather",))
    d = json.loads(fr.json())
    assert d["stage"] == "compile" and d["attempts"] == 3
    assert d["demotions"] == ["strided->gather"]
    # arbitrary context objects were sanitized, not crashed on
    assert isinstance(d["context"]["weird"], str)
    rebuilt = FailureRecord(**d)
    assert rebuilt.label == fr.label and rebuilt.stage == fr.stage


# ---------------------------------------------------------------------------
# adaptive measurement quality
# ---------------------------------------------------------------------------


def test_time_fn_legacy_reps_exact():
    calls = []
    t = time_fn(lambda: calls.append(1), reps=4, warmup=1)
    assert t.reps == 4 and len(t.all_seconds) == 4
    assert len(calls) == 5  # warmup + reps
    assert t.converged and t.target_cv is None
    assert t.minimum == min(t.all_seconds)
    assert t.seconds == sorted(t.all_seconds)[2]


def test_time_fn_adaptive_runs_to_rep_budget_when_cv_unreachable():
    t = time_fn(lambda: None, reps=3, warmup=0, target_cv=0.0, max_reps=9)
    assert t.reps == 9            # CV of real timings never hits exactly 0
    assert not t.converged
    assert t.target_cv == 0.0
    q = t.quality()
    assert {"median_s", "min_s", "cv", "reps", "target_cv", "converged",
            "slow_reps"} <= set(q)
    assert q["reps"] == 9 and q["converged"] is False


def test_time_fn_adaptive_converges_on_loose_target():
    t = time_fn(lambda: None, reps=3, warmup=0, target_cv=1e9, max_reps=50)
    assert t.reps == 3 and t.converged


def test_time_fn_straggler_counting():
    calls = {"i": 0}

    def fn():
        calls["i"] += 1
        time.sleep(0.05 if calls["i"] == 6 else 0.001)

    t = time_fn(fn, reps=6, warmup=1)  # call 6 = timed rep 5 (a straggler)
    assert t.slow_reps >= 1
    assert t.quality()["slow_reps"] >= 1


def test_time_fn_watchdog_raises_budget_exceeded():
    with pytest.raises(BudgetExceeded) as ei:
        time_fn(lambda: time.sleep(0.03), reps=50, warmup=0, budget_s=0.05)
    ctx = ei.value.context
    assert ctx["budget_s"] == 0.05
    assert ctx["elapsed_s"] > 0.05
    assert 0 < ctx["reps_done"] < 50
    assert ei.value.transient  # a retry under calmer load may fit


def test_time_pair_alternates_and_reports_quality():
    order = []
    ta, tb = time_pair(lambda: order.append("a"), (),
                       lambda: order.append("b"), (), reps=3, passes=2,
                       warmup=1)
    # warmup pair first, then strict A/B alternation
    assert order == ["a", "b"] * 7
    assert ta.reps == tb.reps == 6
    assert ta.minimum <= ta.seconds
    assert {"median_s", "min_s", "cv"} <= set(tb.quality())


# ---------------------------------------------------------------------------
# guard rails in the driver
# ---------------------------------------------------------------------------


def test_capacity_preflight_refuses_structured():
    d = Driver(lambda env: triad(),
               dataclasses.replace(CFG, capacity_budget_bytes=1024),
               cache=TranslationCache())
    with pytest.raises(CapacityRefused) as ei:
        d.run([1 << 14])
    ctx = ei.value.context
    assert ctx["required_bytes"] == 2 * ctx["working_set_bytes"]
    assert ctx["required_bytes"] > ctx["budget_bytes"] == 1024
    assert ctx["pattern"] == "triad"
    assert ctx["env"]["n"] == 1 << 14


def test_capacity_preflight_admits_within_budget():
    d = Driver(lambda env: triad(),
               dataclasses.replace(CFG, capacity_budget_bytes=1 << 30),
               cache=TranslationCache())
    (rec,) = d.run([256])
    assert rec.n == 256


def test_records_stamp_timing_quality():
    d = Driver(lambda env: triad(), CFG, cache=TranslationCache())
    (rec,) = d.run([256])
    q = rec.extra["timing_quality"]
    assert q["reps"] == CFG.reps
    assert q["min_s"] <= q["median_s"]


def test_driver_budget_exceeded_carries_context():
    d = Driver(lambda env: triad(),
               dataclasses.replace(CFG, time_budget_s=1e-9, reps=3),
               cache=TranslationCache())
    with pytest.raises(BudgetExceeded) as ei:
        d.run([256])
    assert ei.value.context["template"] == "unified"
    assert ei.value.context["pattern"] == "triad"


# ---------------------------------------------------------------------------
# fault-isolated run_plan
# ---------------------------------------------------------------------------


def _poisoned_factory(env, stride=2):
    if stride == 13:
        raise RuntimeError("injected poison")
    return gather(stride=stride)


def test_poisoned_point_does_not_abort_sweep():
    plan = SweepPlan.product(pattern_axis("stride", (2, 13, 8)),
                             env_axis((256,)))
    report = run_plan(_poisoned_factory, [VariantSpec("g", CFG)], plan,
                      cache=TranslationCache())
    assert [r.point.label for r in report.rows] == ["stride2/n256",
                                                    "stride8/n256"]
    assert [f.label for f in report.failures] == ["stride13/n256"]
    f = report.failures[0]
    assert f.stage == "lower" and f.error == "LowerFailure"
    assert f.context["cause"] == "RuntimeError"
    assert "injected poison" in f.message
    assert f.attempts >= 2 and f.demotions  # the ladder was walked
    assert not report.ok


def test_strict_mode_raises_original_exception():
    plan = SweepPlan.product(pattern_axis("stride", (2, 13)),
                             env_axis((256,)))
    with pytest.raises(RuntimeError, match="injected poison"):
        run_plan(_poisoned_factory, [VariantSpec("g", CFG)], plan,
                 cache=TranslationCache(), on_error="raise")


def test_run_plan_rejects_unknown_on_error():
    with pytest.raises(ValueError, match="on_error"):
        run_plan(lambda env: triad(), [VariantSpec("t", CFG)], _plan(256),
                 cache=TranslationCache(), on_error="ignore")


def test_injected_compile_fault_demotes_to_specialized(monkeypatch):
    """A parametric-only compile fault walks strided->gather (still
    parametric: still broken) then parametric->specialized (works), and
    the records carry the demotion trail — 'demoted-then-recorded'."""
    real = ParamLowered.compile

    def broken(self, **kw):
        raise RuntimeError("parametric compile poisoned")

    monkeypatch.setattr(ParamLowered, "compile", broken)
    cfg = dataclasses.replace(CFG, template="independent", programs=2,
                              parametric="auto", param_path="auto")
    report = run_plan(lambda env: triad(), [VariantSpec("t", cfg)],
                      _plan(256, 512), cache=TranslationCache())
    assert report.ok
    assert [r.point.label for r in report.rows] == ["n256", "n512"]
    steps = [d.step for d in report.demotions]
    assert steps == ["strided->gather", "parametric->specialized"]
    assert report.demotions[0].stage == "compile"
    assert report.demotions[0].error == "CompileFailure"
    for r in report.rows:
        assert r.record.extra["param_path"] == "specialized"
        assert r.record.extra["demotions"] == ["strided->gather",
                                               "parametric->specialized"]
    monkeypatch.setattr(ParamLowered, "compile", real)


def test_demotion_ladder_order_ends_undonated():
    """A fault that only clears once donation is off exercises the full
    ladder in order; the surviving record reports donated=False."""
    calls = {"n": 0}
    real = Driver.measure_point

    def flaky(self, p):
        if getattr(p.compiled, "donated", True):
            raise RuntimeError("donation stream poisoned")
        return real(self, p)

    plan = _plan(256, 512)
    cfg = dataclasses.replace(CFG, template="independent", programs=2,
                              parametric="auto")
    try:
        Driver.measure_point = flaky
        report = run_plan(lambda env: triad(), [VariantSpec("t", cfg)],
                          plan, cache=TranslationCache())
    finally:
        Driver.measure_point = real
    assert report.ok
    steps = [d.step for d in report.demotions]
    assert steps == ["strided->gather", "parametric->specialized",
                     "donated->undonated"]
    for r in report.rows:
        assert r.record.extra["donated"] is False


def test_injected_validate_fault_is_classified(monkeypatch):
    real = Driver.validate

    def bad(self, env=None):
        raise AssertionError("oracle disagrees")

    monkeypatch.setattr(Driver, "validate", bad)
    cfg = dataclasses.replace(CFG, validate_n=64)
    report = run_plan(lambda env: triad(), [VariantSpec("t", cfg)],
                      _plan(256), cache=TranslationCache())
    assert not report.rows
    assert {f.stage for f in report.failures} == {"validate"}
    assert {f.error for f in report.failures} == {"ValidateFailure"}
    monkeypatch.setattr(Driver, "validate", real)
    # strict mode: the original AssertionError propagates
    with pytest.raises(AssertionError, match="oracle disagrees"):
        monkeypatch.setattr(Driver, "validate", bad)
        run_plan(lambda env: triad(), [VariantSpec("t", cfg)], _plan(256),
                 cache=TranslationCache(), on_error="raise")


def test_transient_measure_fault_retries_without_demotion():
    real = Driver.measure_point
    calls = {"n": 0}

    def once_flaky(self, p):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("spurious load spike")
        return real(self, p)

    try:
        Driver.measure_point = once_flaky
        report = run_plan(
            lambda env: triad(), [VariantSpec("t", CFG)], _plan(256),
            cache=TranslationCache(),
            resilience=ResiliencePolicy(max_retries=2, backoff_s=0.0))
    finally:
        Driver.measure_point = real
    assert report.ok and len(report.rows) == 1
    assert not report.demotions  # the retry fixed it inside the same rung


def test_capacity_refusal_isolated_per_point():
    """One oversized point fails with a structured capacity refusal;
    the in-budget point still measures (after parametric demotion —
    the shared executable would allocate everything at capacity)."""
    ws = 3 * 256 * 4  # triad working set at n=256
    cfg = dataclasses.replace(CFG, template="independent", programs=2,
                              parametric="auto",
                              capacity_budget_bytes=8 * ws)
    report = run_plan(lambda env: triad(), [VariantSpec("t", cfg)],
                      _plan(256, 1 << 20), cache=TranslationCache())
    assert [r.point.label for r in report.rows] == ["n256"]
    (f,) = report.failures
    assert f.label == f"n{1 << 20}"
    assert f.stage == "capacity" and f.error == "CapacityRefused"
    assert f.context["required_bytes"] > f.context["budget_bytes"]


def test_multi_group_isolation_other_variant_untouched():
    plan = SweepPlan.product(pattern_axis("stride", (2, 13)),
                             env_axis((256,)))
    report = run_plan(
        _poisoned_factory,
        [VariantSpec("a", CFG),
         VariantSpec("b", dataclasses.replace(CFG, programs=4))],
        plan, cache=TranslationCache())
    assert [(r.variant, r.point.label) for r in report.rows] == [
        ("a", "stride2/n256"), ("b", "stride2/n256")]
    assert {(f.variant, f.label) for f in report.failures} == {
        ("a", "stride13/n256"), ("b", "stride13/n256")}


def test_sweep_failures_aggregate():
    plan = SweepPlan.product(pattern_axis("stride", (13,)), env_axis((256,)))
    report = run_plan(_poisoned_factory, [VariantSpec("g", CFG)], plan,
                      cache=TranslationCache())
    with pytest.raises(SweepFailures) as ei:
        report.raise_if_failed()
    assert ei.value.failures == tuple(report.failures)
    assert "stride13/n256" in str(ei.value)


def test_run_report_sequence_protocol():
    report = run_plan(lambda env: triad(), [VariantSpec("t", CFG)],
                      _plan(256, 512), cache=TranslationCache())
    assert len(report) == 2
    assert [r.point.label for r in report] == ["n256", "n512"]
    assert report[0].variant == "t"
    assert report.ok and report.summary()["failures"] == []


# ---------------------------------------------------------------------------
# resumable journal
# ---------------------------------------------------------------------------


def test_stable_fingerprint_is_deterministic():
    cfg = CFG
    a = stable_fingerprint("v", (("n", "n256"),), "n256", cfg,
                           lambda env: triad())
    b = stable_fingerprint("v", (("n", "n256"),), "n256", cfg,
                           lambda env: triad())
    assert a == b and len(a) == 40
    assert a != stable_fingerprint("v2", (("n", "n256"),), "n256", cfg)
    assert stable_fingerprint(1) != stable_fingerprint("1")
    assert stable_fingerprint(True) != stable_fingerprint(1)


def test_journal_full_replay_byte_identical(tmp_path):
    jpath = tmp_path / "run.jsonl"
    v = [VariantSpec("t", CFG)]
    c1 = TranslationCache()
    r1 = run_plan(lambda env: triad(), v, _plan(256, 512), cache=c1,
                  journal=str(jpath))
    assert r1.replayed == 0 and len(r1.rows) == 2
    assert len(jpath.read_text().splitlines()) == 2
    c2 = TranslationCache()
    r2 = run_plan(lambda env: triad(), v, _plan(256, 512), cache=c2,
                  journal=str(jpath))
    assert r2.replayed == 2
    assert c2.stats()["compile_misses"] == 0  # nothing re-staged
    assert [a.record.json() for a in r1.rows] == \
           [b.record.json() for b in r2.rows]
    assert [a.point.label for a in r1.rows] == \
           [b.point.label for b in r2.rows]


def test_journal_crash_resume_completes_remainder(tmp_path):
    """Kill a journaled sweep mid-run (simulated: truncate the journal
    to its first completed point), re-invoke, and only the remainder
    executes — the replayed row stays byte-identical."""
    jpath = tmp_path / "run.jsonl"
    v = [VariantSpec("t", CFG)]
    full = run_plan(lambda env: triad(), v, _plan(256, 512, 1024),
                    cache=TranslationCache(), journal=str(jpath))
    lines = jpath.read_text().splitlines()
    assert len(lines) == 3
    jpath.write_text(lines[0] + "\n")        # "crash" after point one
    c2 = TranslationCache()
    resumed = run_plan(lambda env: triad(), v, _plan(256, 512, 1024),
                       cache=c2, journal=str(jpath))
    assert resumed.replayed == 1
    assert len(resumed.rows) == 3
    assert c2.stats()["compile_misses"] > 0   # the remainder really ran
    assert resumed.rows[0].record.json() == full.rows[0].record.json()
    assert [r.point.label for r in resumed.rows] == ["n256", "n512",
                                                     "n1024"]
    # and the journal is complete again: a third invocation is all replay
    r3 = run_plan(lambda env: triad(), v, _plan(256, 512, 1024),
                  cache=TranslationCache(), journal=str(jpath))
    assert r3.replayed == 3


def test_journal_tolerates_torn_tail_line(tmp_path):
    jpath = tmp_path / "run.jsonl"
    v = [VariantSpec("t", CFG)]
    run_plan(lambda env: triad(), v, _plan(256, 512),
             cache=TranslationCache(), journal=str(jpath))
    lines = jpath.read_text().splitlines()
    jpath.write_text(lines[0] + "\n" + lines[1][: len(lines[1]) // 2])
    resumed = run_plan(lambda env: triad(), v, _plan(256, 512),
                       cache=TranslationCache(), journal=str(jpath))
    assert resumed.replayed == 1 and len(resumed.rows) == 2


def test_journal_replays_failures_too(tmp_path):
    jpath = tmp_path / "run.jsonl"
    plan = SweepPlan.product(pattern_axis("stride", (2, 13)),
                             env_axis((256,)))
    v = [VariantSpec("g", CFG)]
    r1 = run_plan(_poisoned_factory, v, plan, cache=TranslationCache(),
                  journal=str(jpath))
    assert len(r1.rows) == 1 and len(r1.failures) == 1
    r2 = run_plan(_poisoned_factory, v, plan, cache=TranslationCache(),
                  journal=str(jpath))
    assert r2.replayed == 2          # the failure replays as completed too
    assert len(r2.rows) == 1 and len(r2.failures) == 1
    assert r2.failures[0].label == "stride13/n256"


def test_journal_key_distinguishes_configs(tmp_path):
    jpath = tmp_path / "run.jsonl"
    v1 = [VariantSpec("t", CFG)]
    run_plan(lambda env: triad(), v1, _plan(256),
             cache=TranslationCache(), journal=str(jpath))
    # same variant label, different config -> different key -> re-runs
    v2 = [VariantSpec("t", dataclasses.replace(CFG, ntimes=4))]
    r = run_plan(lambda env: triad(), v2, _plan(256),
                 cache=TranslationCache(), journal=str(jpath))
    assert r.replayed == 0 and len(r.rows) == 1


def test_narrowed_parametric_viability_probe_still_specializes():
    """The narrowed except in _parametric_viable keeps demoting expected
    probe failures (custom kernels, env-dependent structure) to the
    specialized path rather than crashing."""
    from repro.core import pointer_chase

    cfg = dataclasses.replace(CFG, programs=1, parametric="auto")
    d = Driver(lambda env: pointer_chase(), cfg, cache=TranslationCache())
    recs = d.run([128, 256])
    assert [r.extra["param_path"] for r in recs] == ["specialized"] * 2


# ---------------------------------------------------------------------------
# PR-8: concurrent journal writes + threadpool crash-resume + collectives
# ---------------------------------------------------------------------------


def test_concurrent_journal_appends_no_torn_lines(tmp_path):
    """Many threads append rows at once (the ThreadPoolBackend writer
    pattern): every line in the file must parse as a whole JSON entry
    and every key must land in the in-memory map."""
    import threading
    import types

    from repro.core.measure import Record

    jpath = tmp_path / "j.jsonl"
    jr = RunJournal(jpath)
    n_threads, per_thread = 8, 25
    barrier = threading.Barrier(n_threads)

    def rec(i):
        return Record("triad", "unified", "identity", "jax", 256, 3072, 1,
                      2, 1e-6, 1.0, 1.0,
                      extra={"payload": "x" * 512, "i": i})

    def worker(t):
        barrier.wait()
        for i in range(per_thread):
            key = f"{t:02d}-{i:04d}"
            pt = types.SimpleNamespace(label=f"n{t}/{i}")
            jr.append_row(key, "v", pt, rec(i))

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    lines = jpath.read_text().splitlines()
    assert len(lines) == n_threads * per_thread
    keys = set()
    for line in lines:
        e = json.loads(line)  # a torn line would raise here
        assert e["kind"] == "row" and len(e["record"]["extra"]["payload"]) == 512
        keys.add(e["key"])
    assert len(keys) == n_threads * per_thread
    assert len(jr) == n_threads * per_thread
    # a fresh load sees the identical entry set
    assert len(RunJournal(jpath)) == n_threads * per_thread


def test_threadpool_crash_resume_byte_identical(tmp_path):
    """Journaled run under ThreadPoolBackend, 'crash' (truncate), resume
    under ThreadPoolBackend: replayed rows byte-identical to the
    original run, remainder re-executes, final row order = plan order."""
    from repro.suite import ThreadPoolBackend

    jpath = tmp_path / "run.jsonl"
    v = [VariantSpec("t", CFG)]
    plan = SweepPlan.product(env_axis((256, 512, 1024)))
    full = run_plan(lambda env: triad(), v, plan, cache=TranslationCache(),
                    journal=str(jpath), backend=ThreadPoolBackend(3))
    assert len(full.rows) == 3
    lines = jpath.read_text().splitlines()
    assert len(lines) == 3
    jpath.write_text(lines[0] + "\n")        # crash after one entry
    c2 = TranslationCache()
    resumed = run_plan(lambda env: triad(), v, plan, cache=c2,
                       journal=str(jpath), backend=ThreadPoolBackend(3))
    assert resumed.replayed == 1
    assert len(resumed.rows) == 3
    assert c2.stats()["compile_misses"] > 0   # the remainder really ran
    assert [r.point.label for r in resumed.rows] == ["n256", "n512",
                                                     "n1024"]
    replayed_label = json.loads(lines[0])["label"]
    (orig,) = [r for r in full.rows if r.point.label == replayed_label]
    (rep,) = [r for r in resumed.rows if r.point.label == replayed_label]
    assert orig.record.json() == rep.record.json()
    # the journal is whole again: a serial re-run is all replay
    r3 = run_plan(lambda env: triad(), v, plan, cache=TranslationCache(),
                  journal=str(jpath))
    assert r3.replayed == 3


def test_collective_wire_byte_formulas():
    from repro.suite import expected_wire_bytes

    # all_gather over k devices: (k-1)/k of the gathered k*S*4 bytes
    assert expected_wire_bytes("all_gather", 1024, 8) == 7 / 8 * 8 * 1024 * 4
    # all_reduce: reduce-scatter + all-gather = 2(k-1)/k of S*4 bytes
    assert expected_wire_bytes("all_reduce", 1024, 8) == 2 * 7 / 8 * 1024 * 4
    # degenerate 1-device mesh: no wire traffic at all
    assert expected_wire_bytes("all_gather", 1024, 1) == 0
    assert expected_wire_bytes("all_reduce", 1024, 1) == 0
    with pytest.raises(ValueError, match="unknown collective"):
        expected_wire_bytes("all_to_all", 1024, 8)


def test_collective_ladder_skips_on_single_device(capsys):
    """On a 1-device box (the default test process) the ladder measures
    nothing and the runner emits the skip comment."""
    import jax

    from repro.suite import collective_runner, measure_collectives

    if len(jax.devices()) != 1:  # pragma: no cover - forced-device env
        pytest.skip("multi-device environment")
    assert measure_collectives(quick=True) == []
    lines = collective_runner(quick=True)
    assert len(lines) == 1 and lines[0].startswith("# collective ladder skipped")


@pytest.mark.slow
def test_collective_ladder_agreement_on_forced_mesh(tmp_path):
    """Ring accounting and analyze_collectives must agree within 10% on
    a forced 8-device host mesh (subprocess: device count is fixed at
    jax import)."""
    import os
    import subprocess
    import sys

    code = (
        "import os, json\n"
        "os.environ['XLA_FLAGS'] = "
        "'--xla_force_host_platform_device_count=8'\n"
        "from repro.suite import measure_collectives\n"
        "print(json.dumps(measure_collectives(quick=True)))\n"
    )
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [p for p in env.get("PYTHONPATH", "").split(os.pathsep) if p]
        + [str(__import__("pathlib").Path(__file__).resolve().parents[1]
               / "src")])
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    rows = json.loads(out.stdout.strip().splitlines()[-1])
    assert {r["op"] for r in rows} == {"all_gather", "all_reduce"}
    assert all(r["devices"] == 8 for r in rows)
    for r in rows:
        assert abs(r["agreement"] - 1.0) <= 0.10, r
        assert r["values_ok"], r
        assert r["gbs"] > 0 and r["seconds"] > 0
