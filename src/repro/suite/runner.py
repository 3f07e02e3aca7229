"""The one executor every registered workload shares.

Per workload: expand the sweep plan (a legacy ladder is a one-axis
plan), hand it to the plan engine — which stages every (variant, point)
executable up front, shares one executable along parametric env axes,
and validates each distinct executable once against the serial oracle —
then emit the paper's ``name,us_per_call,derived`` CSV contract. The
per-workload translation activity is reported as a cache-delta comment
line.
"""
from __future__ import annotations

import dataclasses

from repro.core import GLOBAL_CACHE, Record, TranslationCache
from repro.core.errors import ResiliencePolicy

from .engine import ExecutionBackend, RunReport, run_plan
from .journal import RunJournal
from .registry import load_builtins, workload as _lookup
from .workload import Workload

__all__ = ["csv_line", "emit", "run_workload", "run_module",
           "collect_records", "collect_report"]


def csv_line(name: str, rec: Record, derived: str | float = "") -> str:
    if derived == "":
        derived = f"{rec.gbs:.3f}GB/s"
    return f"{name},{rec.seconds * 1e6:.2f},{derived}"


def emit(lines: list[str]) -> list[str]:
    for ln in lines:
        print(ln, flush=True)
    return lines


def collect_report(
    w: Workload, quick: bool = True, *,
    cache: TranslationCache | None = None,
    parametric: "bool | str | None" = None,
    param_path: str | None = None,
    on_error: str = "demote",
    resilience: ResiliencePolicy | None = None,
    journal: "RunJournal | str | None" = None,
    backend: "ExecutionBackend | None" = None,
) -> RunReport:
    """Measure a declarative workload through the fault-isolated plan
    engine; returns the full :class:`~repro.suite.engine.RunReport`
    (rows + failures + demotions + journal replays + executor stats).
    ``backend`` picks the execution backend (None = serial)."""
    if w.runner is not None:
        raise ValueError(f"workload {w.name!r} is custom; run it via run_workload")
    cache = cache if cache is not None else GLOBAL_CACHE
    return run_plan(
        w.pattern, w.variant_list(quick), w.sweep_plan(),
        quick=quick, cache=cache, validate=w.validate,
        parametric=w.parametric if parametric is None else parametric,
        param_path=param_path, on_error=on_error, resilience=resilience,
        journal=journal, backend=backend,
    )


def collect_records(
    w: Workload, quick: bool = True, *,
    cache: TranslationCache | None = None,
    parametric: "bool | str | None" = None,
    param_path: str | None = None,
) -> list[tuple[str, Record]]:
    """Measure a declarative workload; returns ``(csv_label, record)``
    pairs. This is the runner's core loop, exposed so tests can compare
    parametric-vs-specialized executions of every registered workload.
    ``parametric`` overrides the workload-level policy (None = use it);
    ``param_path`` pins the parametric lowering regime on configs that
    leave it at "auto" (the regime-conformance tests run every workload
    under "gather" and "strided" and demand identical records).

    Strict by contract: a fault propagates with its original exception
    class (the conformance tests assert on exact classes). Callers that
    want fault isolation use :func:`collect_report`.
    """
    report = collect_report(w, quick, cache=cache, parametric=parametric,
                            param_path=param_path, on_error="raise")
    return [
        (f"{w.figure}/{row.variant}/{row.point.label}", row.record)
        for row in report.rows
    ]


def run_workload(w: Workload, quick: bool = True, *,
                 cache: TranslationCache | None = None,
                 journal: "RunJournal | str | None" = None,
                 backend: "ExecutionBackend | None" = None,
                 executor_stats: "dict | None" = None,
                 demotions: "list | None" = None) -> list[str]:
    """Execute one workload (declarative or custom) and emit its CSV.

    Fault-isolated: a failing plan point is demoted/retried by the
    engine and, if it still fails, reported as a ``# FAILED`` comment
    while every surviving row is emitted normally; the aggregated
    :class:`~repro.core.errors.SweepFailures` (carrying the
    ``FailureRecord`` list on ``.failures``) is raised *after* emission
    so batch callers (``benchmarks/run.py``) can record the failure and
    continue to the next workload.

    ``backend`` picks the plan engine's execution backend (custom-runner
    workloads ignore it — they own their execution). When the caller
    passes an ``executor_stats`` dict, the report's per-phase executor
    accounting is copied into it (the ledger's stage/measure split); a
    ``demotions`` list receives every demotion-ladder step taken, as a
    dict (a demoted point ran under another config than declared).
    """
    if w.runner is not None:
        return list(w.runner(quick))
    cache = cache if cache is not None else GLOBAL_CACHE
    s0 = cache.stats()
    report = collect_report(w, quick, cache=cache, journal=journal,
                            backend=backend)
    if executor_stats is not None:
        executor_stats.update(report.executor)
    lines = [
        csv_line(f"{w.figure}/{row.variant}/{row.point.label}", row.record,
                 w.derived(row.record) if w.derived else "")
        for row in report.rows
    ]
    if w.post is not None:
        lines.extend(w.post(quick))
    s1 = cache.stats()
    print(
        f"# {w.name} cache: "
        f"{s1['compile_hits'] - s0['compile_hits']} compile hits / "
        f"{s1['compile_misses'] - s0['compile_misses']} misses",
        flush=True,
    )
    if report.replayed:
        print(f"# {w.name} journal: {report.replayed} point(s) replayed",
              flush=True)
    for d in report.demotions:
        print(f"# {w.name} demoted [{d.step}] after {d.stage}:{d.error} "
              f"({', '.join(d.labels)})", flush=True)
        if demotions is not None:
            demotions.append(dataclasses.asdict(d))
    for f in report.failures:
        print(f"# {w.name} FAILED {f.variant}/{f.label}: "
              f"{f.stage}:{f.error}: {f.message}", flush=True)
    emit(lines)
    report.raise_if_failed()
    return lines


def run_module(name: str, quick: bool = True) -> list[str]:
    """Registry lookup + run — the body of every thin ``fig*`` module."""
    load_builtins()
    return run_workload(_lookup(name), quick)
