"""The plan-based execution engine — one executor for every sweep.

Everything that measures (the workload runner, ``autotune.sweep``, the
registered scenarios) converges here: a :class:`~repro.suite.axes.SweepPlan`
expands into labelled points, the engine partitions them into *driver
groups* (all points sharing config overrides and pattern kwargs — i.e.
differing only along env axes — regardless of axis order; results are
re-emitted in plan order), and each group executes through the staged
lower→compile pipeline:

* **env axes** form the group's working-set ladder. Where the schedule
  lowers symbolically the whole group shares ONE parametric executable
  (the PR 2 regime); otherwise each env point specializes, with the
  translation cache deduplicating identical tuples across groups,
  variants, and re-runs.
* **config / pattern axes** change the executable's structure, so each
  distinct combination is its own specialization — staged up front so
  the XLA compiles overlap on worker threads.

Each distinct executable is validated once against the serial oracle
(memoized in the cache), and every record is annotated with
``extra["axis_point"]`` — the axis-name → point mapping — so CSVs stay
self-describing however many axes a scenario sweeps.

Fault isolation (``on_error="demote"``, the default): a faulting group
never aborts the sweep. Transient faults retry with bounded exponential
backoff (:class:`~repro.core.errors.ResiliencePolicy`); persistent ones
walk the **demotion ladder** — strided→gather, parametric→per-size
specialized, donated→undonated — re-attempting only the group's still
-pending points at each rung; a group that exhausts the ladder marks
*its own* points failed and the sweep continues. The result is a
:class:`RunReport` (rows + failures + demotions) instead of a bare row
list; ``on_error="raise"`` reproduces the strict legacy behavior
(original exceptions propagate — the conformance tests depend on the
exact classes). Plan-*shape* errors (missing 'n' env axis, zip-length
mismatch, unknown variant wiring) always raise: a malformed plan is a
bug, not a fault to survive.

Resumability: ``run_plan(journal=path)`` appends each completed point
to a :class:`~repro.suite.journal.RunJournal`; re-invocation replays
completed keys (byte-identical records, zero compiles) and executes
only the remainder.

Execution backends (``run_plan(backend=...)``): *how* the live groups
stage and measure is pluggable. :class:`SerialBackend` (the default)
reproduces the legacy order exactly — one process-wide staging barrier,
then groups measured one at a time in plan order. :class:`ThreadPool
Backend` removes the barrier: each worker stages its group and
immediately measures it, so group N+1's lower/compile overlaps group
N's timing loop (XLA compiles release the GIL). The determinism
contract both backends honour: the merged record set is byte-identical
modulo timing (rows re-emitted in plan order, per-group fault isolation
and the demotion ladder unchanged, journal appends serialized). To keep
the timings themselves trustworthy, ThreadPoolBackend serializes the
*measurement* phase per resolved device — groups pinned to distinct
devices (the plan's device axis) time genuinely in parallel, while
groups sharing a device never time against each other's noise; the
concurrency win comes from overlapping staging with measurement, not
from timing concurrently on shared hardware.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, Sequence

import jax

from repro.core import (
    Driver,
    GLOBAL_CACHE,
    Record,
    TranslationCache,
    identity,
    precompile,
)
from repro.core.errors import (
    BenchFailure,
    Demotion,
    FailureRecord,
    ResiliencePolicy,
    SweepFailures,
    classify_failure,
)
from repro.core.spans import span

from .axes import PlanPoint, SweepPlan
from .journal import RunJournal
from .workload import VariantSpec

__all__ = [
    "PlanRow",
    "RunReport",
    "run_plan",
    "ExecutionBackend",
    "SerialBackend",
    "ThreadPoolBackend",
]


@dataclasses.dataclass(frozen=True)
class PlanRow:
    """One measured (variant, plan point) result."""

    variant: str
    point: PlanPoint
    record: Record


@dataclasses.dataclass
class RunReport:
    """What a fault-isolated sweep actually produced.

    Iterates like the row list ``run_plan`` used to return (existing
    callers keep working); ``failures`` holds one
    :class:`~repro.core.errors.FailureRecord` per point that exhausted
    the demotion ladder, ``demotions`` the ladder steps taken, and
    ``replayed`` the number of points served from the journal."""

    rows: list[PlanRow]
    failures: list[FailureRecord] = dataclasses.field(default_factory=list)
    demotions: list[Demotion] = dataclasses.field(default_factory=list)
    replayed: int = 0
    # Execution-phase accounting from the backend that ran the sweep:
    # {backend, workers, groups, stage_seconds, measure_seconds,
    #  stage_wall_seconds, first_measure_seconds,
    #  staging_overlap_seconds, wall_seconds}. staging_overlap_seconds
    # is the staging time spent while some group was measuring — 0.0 by
    # construction under SerialBackend (barrier first), positive when
    # ThreadPoolBackend actually pipelined.
    executor: dict = dataclasses.field(default_factory=dict)

    def __iter__(self):
        return iter(self.rows)

    def __len__(self) -> int:
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i]

    @property
    def ok(self) -> bool:
        return not self.failures

    def summary(self) -> dict:
        return {
            "rows": len(self.rows),
            "replayed": self.replayed,
            "failures": [f.as_dict() for f in self.failures],
            "demotions": [dataclasses.asdict(d) for d in self.demotions],
            "executor": dict(self.executor),
        }

    def raise_if_failed(self) -> None:
        """Strictness on demand: aggregate the failures into one
        :class:`~repro.core.errors.SweepFailures` (carrying them on
        ``.failures``) after the surviving rows were already emitted."""
        if self.failures:
            raise SweepFailures(self.failures)


@dataclasses.dataclass
class _Group:
    """Plan points differing only along env axes: one driver, one
    (possibly parametric) prepare/run call. ``order`` holds each point's
    index in the expanded plan so results can be re-emitted in plan
    order whatever the axis ordering was."""

    variant: VariantSpec
    points: list[PlanPoint]
    order: list[int]
    driver: Driver

    @property
    def envs(self) -> list[dict]:
        return [dict(p.env) for p in self.points]


def _wrap_factory(base: Callable, kwargs: tuple) -> Callable:
    """Bind pattern-axis kwargs onto a factory; identity when empty so
    kwarg-less legacy factories (``lambda env: triad()``) keep working."""
    if not kwargs:
        return base
    kw = dict(kwargs)
    return lambda env: base(env, **kw)


def _grouped(variant: VariantSpec, base_factory: Callable | None,
             points: Sequence[PlanPoint], cache: TranslationCache,
             parametric, param_path: str | None) -> list[_Group]:
    """Partition a variant's plan points by (config, pattern) identity.

    Grouping is global, not run-length: an env axis ordered *before* a
    config/pattern axis still lands all of a combination's env points in
    one group, so parametric sharing never depends on axis order."""
    factory = variant.pattern or base_factory
    if factory is None:
        raise ValueError(f"variant {variant.label!r} has no pattern factory")
    groups: dict[tuple, _Group] = {}
    for i, pt in enumerate(points):
        if "n" not in dict(pt.env):
            raise ValueError(
                f"plan point {pt.label!r} has no 'n' env entry; every plan "
                "needs an env axis targeting the working-set parameter 'n' "
                "(further env axes may add other parameters on top)"
            )
        g = groups.get(pt.group_key)
        if g is not None:
            g.points.append(pt)
            g.order.append(i)
            continue
        cfg = variant.resolved_config()
        if pt.config:
            cfg = dataclasses.replace(cfg, **dict(pt.config))
        if cfg.parametric is None and parametric is not None:
            cfg = dataclasses.replace(cfg, parametric=parametric)
        if param_path is not None and cfg.param_path == "auto":
            cfg = dataclasses.replace(cfg, param_path=param_path)
        drv = Driver(_wrap_factory(factory, pt.pattern_kwargs), cfg,
                     cache=cache)
        groups[pt.group_key] = _Group(
            variant=variant, points=[pt], order=[i], driver=drv
        )
    return list(groups.values())


# ---------------------------------------------------------------------------
# Fault-isolated group execution
# ---------------------------------------------------------------------------


def _demotion_ladder(cfg) -> list[tuple]:
    """The (config, step-name) sequence a failing group walks, most
    capable config first. Each rung trades capability for robustness:

    * ``pallas->jax``         structural backend demotion: patterns the
                              pallas backend refuses (custom kernels,
                              guarded schedules, non-unit vector
                              strides) re-run on the jax backend
                              instead of failing the group;
    * ``strided->gather``     keep sharing one executable, drop the
                              dynamic-slice fast path for the masked
                              gather form that is safe at every env;
    * ``parametric->specialized``  give up executable sharing, one
                              per-size compile per point (isolates both
                              compile faults and capacity-sized
                              allocations to single points);
    * ``donated->undonated``  per-call buffer copies, but no donation
                              stream to corrupt.
    """
    rungs = [(cfg, None)]
    if cfg.backend == "pallas":
        # every later rung runs on jax too: a fault that survives the
        # backend demotion is not a pallas-specific fault
        cfg = dataclasses.replace(cfg, backend="jax")
        rungs.append((cfg, "pallas->jax"))
    if cfg.parametric and cfg.param_path != "gather":
        rungs.append((dataclasses.replace(cfg, param_path="gather"),
                      "strided->gather"))
    if cfg.parametric:
        rungs.append((dataclasses.replace(cfg, parametric=False),
                      "parametric->specialized"))
    if cfg.donate is not False and cfg.backend == "jax":
        rungs.append((dataclasses.replace(cfg, parametric=False,
                                          donate=False),
                      "donated->undonated"))
    return rungs


def _validate_group(d: Driver, envs: list[dict], validate: bool) -> None:
    if validate and d.cfg.validate_n:
        # non-"n" env entries (extra env axes) must reach the
        # oracle too; take them from the group's smallest point
        extra = {k: v for k, v in
                 min(envs, key=lambda e: e["n"]).items() if k != "n"}
        d.validate({**extra, "n": d.cfg.validate_n})


def _attempt_strict(d: Driver, envs: list[dict], validate: bool,
                    max_check_n: int) -> list[Record]:
    """Legacy semantics: any fault propagates with its original class."""
    preps = d.prepare(envs, parallel=False)
    _validate_group(d, envs, validate)
    recs = [d.measure_point(p) for p in preps]
    if validate and d.cfg.validate_n and any(
            r.extra.get("parametric") for r in recs):
        # the executable that produced these numbers is the shared
        # parametric one — oracle-check it too (small points only:
        # the serial oracle's guarded fallback is O(points) Python);
        # memoized per ladder, so re-runs don't re-pay it.
        d.validate_parametric(envs, max_check_n=max_check_n)
    return recs


def _attempt(d: Driver, envs: list[dict], validate: bool, max_check_n: int,
             ctx: dict):
    """One fault-isolated pass over a group's pending envs.

    Group-scope faults (prepare / oracle validation) raise a classified
    ``BenchFailure``; point-scope faults (measurement) are captured per
    point. Returns ``(successes, point_failures)`` as lists of
    (env-index, Record) / (env-index, BenchFailure)."""
    try:
        preps = d.prepare(envs, parallel=False)
    except Exception as e:
        raise classify_failure(e, "lower", **ctx)
    try:
        _validate_group(d, envs, validate)
    except Exception as e:
        raise classify_failure(e, "validate", **ctx)
    recs: list[tuple[int, Record]] = []
    fails: list[tuple[int, BenchFailure]] = []
    for i, p in enumerate(preps):
        try:
            recs.append((i, d.measure_point(p)))
        except Exception as e:
            fails.append((i, classify_failure(e, "measure", **ctx,
                                              env=dict(p.env))))
    if validate and d.cfg.validate_n and any(
            r.extra.get("parametric") for _, r in recs):
        try:
            d.validate_parametric(envs, max_check_n=max_check_n)
        except Exception as e:
            # the shared executable is untrustworthy: every record it
            # produced goes back to pending via the group-scope raise
            raise classify_failure(e, "validate", **ctx)
    return recs, fails


def _run_group_isolated(g: _Group, validate: bool, max_check_n: int,
                        policy: ResiliencePolicy):
    """Walk the demotion ladder for one group; returns
    ``(results, failures, demotions)`` where results maps the group-local
    point index to its Record and failures maps it to the final
    BenchFailure."""
    ctx = {
        "variant": g.variant.label,
        "template": g.driver.cfg.template,
        "backend": g.driver.cfg.backend,
    }
    pending = list(range(len(g.points)))
    results: dict[int, Record] = {}
    last_fail: dict[int, BenchFailure] = {}
    attempts: dict[int, int] = {i: 0 for i in pending}
    demotions: list[Demotion] = []
    steps: tuple[str, ...] = ()
    ladder = _demotion_ladder(g.driver.cfg) if policy.demote \
        else [(g.driver.cfg, None)]
    for cfg, step in ladder:
        if not pending:
            break
        if step is None:
            driver = g.driver
        else:
            trigger = last_fail.get(pending[0])
            demotions.append(Demotion(
                variant=g.variant.label,
                labels=tuple(g.points[i].label for i in pending),
                step=step,
                stage=trigger.stage if trigger else "",
                error=type(trigger).__name__ if trigger else "",
            ))
            steps += (step,)
            driver = Driver(g.driver.factory, cfg, cache=g.driver.cache)
        retry = 0
        while pending:
            if retry:
                time.sleep(policy.backoff_s * (2 ** (retry - 1)))
            cur = list(pending)
            envs = [dict(g.points[i].env) for i in cur]
            try:
                recs, fails = _attempt(driver, envs, validate, max_check_n,
                                       ctx)
            except BenchFailure as e:
                for i in cur:
                    last_fail[i] = e
                    attempts[i] += 1
                if not (e.transient and retry < policy.max_retries):
                    break  # next ladder rung
                retry += 1
                continue
            for li, rec in recs:
                gi = cur[li]
                if steps:
                    rec.extra["demotions"] = list(steps)
                results[gi] = rec
                attempts[gi] += 1
            transient_left = False
            pending = []
            for li, exc in fails:
                gi = cur[li]
                last_fail[gi] = exc
                attempts[gi] += 1
                pending.append(gi)
                transient_left = transient_left or exc.transient
            if not pending:
                break
            if not (transient_left and retry < policy.max_retries):
                break  # next ladder rung
            retry += 1
    failures = {i: last_fail[i] for i in pending}
    return results, failures, demotions, attempts, steps


def _failure_record(g: _Group, i: int, exc: BenchFailure, attempts: int,
                    steps: tuple) -> FailureRecord:
    pt = g.points[i]
    cfg = g.driver.cfg
    try:
        pattern = g.driver.factory(dict(pt.env)).name
    except Exception:
        pattern = str(exc.context.get("pattern", ""))
    return FailureRecord(
        variant=g.variant.label,
        label=pt.label,
        stage=exc.stage,
        error=type(exc).__name__,
        message=str(exc),
        pattern=pattern,
        template=cfg.template,
        schedule=(cfg.schedule or identity()).name,
        backend=cfg.backend,
        env=dict(pt.env),
        axis_point=pt.axis_point(),
        context={**exc.context,
                 "cause": type(exc.cause).__name__ if exc.cause else None},
        attempts=attempts,
        demotions=list(steps),
    )


# ---------------------------------------------------------------------------
# Execution backends
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _GroupRun:
    """One live group's unit of work: a staging step plus a measured
    run, with the outcome captured on the unit itself so backends can
    execute in any order and ``run_plan`` merges deterministically."""

    variant: VariantSpec
    group: _Group
    validate: bool
    max_check_n: int
    policy: ResiliencePolicy
    strict: bool
    jr: "RunJournal | None"
    keys: "list | None"
    rows: list = dataclasses.field(default_factory=list)   # (plan idx, PlanRow)
    failures: list = dataclasses.field(default_factory=list)
    demotions: list = dataclasses.field(default_factory=list)
    # journal lines built during run(), written by flush_journal()
    pending_journal: list = dataclasses.field(default_factory=list)
    error: "BaseException | None" = None
    measure_interval: "tuple | None" = None

    @property
    def device_key(self):
        """Measurement-serialization key: the id of the *physical*
        device this group's kernels run on, not the raw ``cfg.device``
        index. Drivers resolve pins modulo the visible device count
        (``Driver._device``) and ``None`` executes on the process
        default device, so dev0/dev1 on a one-device host — or a pinned
        dev0 group next to an unpinned group — must share one lock;
        keying on the raw index would let them time concurrently on the
        same hardware."""
        dev = self.group.driver._device()
        if dev is None:
            dev = jax.devices()[0]
        return dev.id

    def stage(self) -> None:
        """Lower + compile this group's executables (cache-deduplicated
        against every other group). In the fault-isolated mode a staging
        error is swallowed here and re-surfaces (classified) inside
        ``run``'s own attempt, so one bad group cannot abort staging."""
        try:
            self.group.driver.prepare(self.group.envs, parallel=False)
        except Exception:
            if self.strict:
                raise

    def run(self) -> None:
        """Measure the group (everything below is today's per-group loop
        body, unchanged — demotion ladder and all). Journal lines are
        only *queued* here; the backend calls :meth:`flush_journal`
        afterwards so the journal's flush+fsync never runs under a
        measurement lock, where a slow disk would serialize into other
        groups' time-to-measure."""
        v, g = self.variant, self.group
        if self.strict:
            recs = _attempt_strict(g.driver, g.envs, self.validate,
                                   self.max_check_n)
            for i, pt, rec in zip(g.order, g.points, recs):
                rec.extra["axis_point"] = pt.axis_point()
                self.rows.append((i, PlanRow(v.label, pt, rec)))
        else:
            results, failures, demotions, attempts, steps = \
                _run_group_isolated(g, self.validate, self.max_check_n,
                                    self.policy)
            self.demotions.extend(demotions)
            for li, rec in sorted(results.items()):
                pt = g.points[li]
                rec.extra["axis_point"] = pt.axis_point()
                self.rows.append((g.order[li], PlanRow(v.label, pt, rec)))
            for li, exc in sorted(failures.items()):
                fr = _failure_record(g, li, exc, attempts[li], steps)
                self.failures.append(fr)
                if self.jr is not None:
                    self.pending_journal.append(
                        ("failure", self.keys[li], g.points[li], fr))
        if self.jr is not None:
            for order_i, row in self.rows:
                li = g.order.index(order_i)
                self.pending_journal.append(
                    ("row", self.keys[li], row.point, row.record))

    def flush_journal(self) -> None:
        """Append this unit's queued journal lines (failures first, then
        rows — the order the inline appends used to produce). Backends
        call this exactly once per successfully-run unit, after
        releasing any measurement serialization."""
        if self.jr is None:
            return
        v = self.variant
        for kind, key, point, payload in self.pending_journal:
            if kind == "row":
                self.jr.append_row(key, v.label, point, payload)
            else:
                self.jr.append_failure(key, v.label, point, payload)
        self.pending_journal.clear()


class ExecutionBackend:
    """How live driver groups stage and measure.

    ``execute(units, strict)`` must (1) call every unit's ``stage``,
    then ``run``, then — once ``run`` succeeded and any measurement
    serialization is released — ``flush_journal``, each exactly once,
    (2) record each unit's measurement span on
    ``unit.measure_interval``, (3) return the list of staging
    ``(start, end)`` spans it spent, and (4) surface unit errors: under
    ``strict`` the first error in unit (= plan) order propagates after
    all workers settle; outside strict any escaped exception is a plan
    bug and propagates too. Result *merging* is not the backend's job —
    outcomes accumulate on the units and ``run_plan`` re-emits them in
    plan order, which is what keeps the record set byte-identical
    across backends."""

    name = "?"
    workers = 1

    def execute(self, units: "list[_GroupRun]",
                strict: bool) -> "list[tuple[float, float]]":
        raise NotImplementedError


class SerialBackend(ExecutionBackend):
    """The legacy order, exactly: stage every group's executables behind
    one ``precompile`` barrier (compiles overlap on worker threads, as
    before), then measure the groups one at a time in plan order."""

    name = "serial"
    workers = 1

    def execute(self, units, strict):
        if not units:
            return []
        with span("repro.group.stage", groups=len(units)) as st:
            precompile([u.stage for u in units])
        for u in units:
            with span("repro.group.measure") as m:
                u.run()
            u.measure_interval = (m.start, m.end)
            u.flush_journal()
        return [(st.start, st.end)]


class ThreadPoolBackend(ExecutionBackend):
    """Overlapped staging: no global barrier. Each worker stages its
    group then immediately measures it, so group N+1's lower/compile
    (GIL-released XLA) runs while group N times. Measurement itself is
    serialized per resolved *physical* device — a per-device lock keyed
    on the device each group actually runs on — so timings are never
    taken concurrently on shared hardware; device-axis groups pinned to
    distinct devices do measure in parallel.

    CPU-backend caveat: on CPU-only hosts (the CI configuration) the
    overlapped XLA compiles run on the same cores as the kernel under
    test, so the per-device lock cannot stop compile threads from
    adding measurement noise — the adaptive ``target_cv`` rep
    escalation absorbs it, at the cost of extra reps. On accelerator
    backends compiles burn host cores while kernels time on the device,
    and the overlap is noise-free."""

    name = "threadpool"

    def __init__(self, workers: int = 4):
        if workers < 1:
            raise ValueError(f"ThreadPoolBackend needs >=1 worker, got "
                             f"{workers}")
        self.workers = int(workers)
        self._locks: dict = {}
        self._locks_guard = threading.Lock()

    def _measure_lock(self, key) -> threading.Lock:
        with self._locks_guard:
            return self._locks.setdefault(key, threading.Lock())

    def execute(self, units, strict):
        stage_intervals: list[tuple[float, float]] = []
        si_guard = threading.Lock()

        def work(u: _GroupRun) -> None:
            with span("repro.group.stage", groups=1) as st:
                try:
                    u.stage()          # swallows faults unless strict
                except Exception as e:
                    u.error = e
            with si_guard:
                stage_intervals.append((st.start, st.end))
            if u.error is not None:
                return
            with self._measure_lock(u.device_key):
                with span("repro.group.measure") as m:
                    try:
                        u.run()
                    except Exception as e:
                        u.error = e
                u.measure_interval = (m.start, m.end)
            if u.error is None:
                try:
                    u.flush_journal()   # outside the measure lock
                except Exception as e:
                    u.error = e

        if units:
            with ThreadPoolExecutor(max_workers=self.workers,
                                    thread_name_prefix="plan-exec") as pool:
                list(pool.map(work, units))
        # deterministic error surfacing: first failed unit in plan order
        # (under strict these are the legacy exception classes; outside
        # strict an escaped exception is a plan bug, not a fault)
        for u in units:
            if u.error is not None:
                raise u.error
        return stage_intervals


def _overlap_seconds(stage_intervals, measure_intervals) -> float:
    """Total staging time that ran while some measurement was running —
    the pipelining the ThreadPoolBackend exists to create."""
    measure_intervals = [m for m in measure_intervals if m is not None]
    if not stage_intervals or not measure_intervals:
        return 0.0
    merged: list[list[float]] = []
    for a, b in sorted(measure_intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    total = 0.0
    for s0, s1 in stage_intervals:
        for m0, m1 in merged:
            lo, hi = max(s0, m0), min(s1, m1)
            if hi > lo:
                total += hi - lo
    return total


def run_plan(
    factory: Callable | None,
    variants: Sequence[VariantSpec],
    plan: SweepPlan,
    *,
    quick: bool = True,
    cache: TranslationCache | None = None,
    validate: bool = True,
    parametric: "bool | str | None" = None,
    param_path: str | None = None,
    max_check_n: int = 4096,
    on_error: str = "demote",
    resilience: ResiliencePolicy | None = None,
    journal: "RunJournal | str | None" = None,
    backend: "ExecutionBackend | None" = None,
) -> RunReport:
    """Execute ``plan`` under every variant; returns a :class:`RunReport`
    whose rows iterate in variant-major, plan-point order.

    ``parametric`` is the env-axis-sharing policy applied to configs
    that leave ``DriverConfig.parametric`` unset (None leaves them
    unset — the driver then specializes). ``param_path`` likewise pins
    the parametric lowering regime ("strided"/"gather") on configs that
    leave it at "auto" — the conformance tests use it to run a whole
    registry under one regime. Every group's executables are staged
    before any timing starts; validation runs once per distinct
    executable (cache-memoized), with the parametric oracle replay
    bounded to points ``<= max_check_n``.

    ``on_error="demote"`` (default) isolates faults per driver group —
    retry/backoff per ``resilience``, then the demotion ladder, then
    only that group's points land in ``report.failures``;
    ``on_error="raise"`` propagates the first fault with its original
    exception class (strict legacy behavior). ``journal`` (a path or
    :class:`~repro.suite.journal.RunJournal`) makes the run resumable:
    completed points replay, only the remainder executes.

    ``backend`` picks the execution backend (default
    :class:`SerialBackend`). :class:`ThreadPoolBackend` stages and
    measures groups concurrently with staging overlapped into
    measurement; the merged record set is byte-identical modulo timing
    either way, and ``report.executor`` carries the phase accounting.
    """
    if on_error not in ("demote", "raise"):
        raise ValueError(
            f"unknown on_error {on_error!r} (expected 'demote' or 'raise')")
    cache = cache if cache is not None else GLOBAL_CACHE
    policy = resilience if resilience is not None else ResiliencePolicy()
    exec_backend = backend if backend is not None else SerialBackend()
    strict = on_error == "raise"
    jr = None
    if journal is not None:
        jr = journal if isinstance(journal, RunJournal) else RunJournal(journal)
    points = plan.points(quick)
    per_variant = [
        (v, _grouped(v, factory, points, cache, parametric, param_path))
        for v in variants
    ]
    report = RunReport(rows=[])

    # journal replay: resolve every already-completed point up front and
    # shrink the groups to the remainder
    keyed: dict[int, list] = {}
    replayed: dict[int, list] = {}
    if jr is not None:
        for vi, (v, gs) in enumerate(per_variant):
            for gi, g in enumerate(gs):
                keys = [RunJournal.key_for(v.label, pt, g.driver.cfg,
                                           g.driver.factory)
                        for pt in g.points]
                keyed[id(g)] = keys
                live_points, live_order, live_keys = [], [], []
                rep: list[tuple[int, PlanRow]] = []
                for pt, order_i, key in zip(g.points, g.order, keys):
                    entry = jr.seen(key)
                    if entry is None:
                        live_points.append(pt)
                        live_order.append(order_i)
                        live_keys.append(key)
                        continue
                    report.replayed += 1
                    if entry["kind"] == "row":
                        rec = Record(**entry["record"])
                        rep.append((order_i, PlanRow(v.label, pt, rec)))
                    else:
                        report.failures.append(
                            FailureRecord(**entry["failure"]))
                replayed[id(g)] = rep
                g.points, g.order = live_points, live_order
                keyed[id(g)] = live_keys

    # one work unit per live group, in variant-major plan order — the
    # order SerialBackend executes in and every backend's error /
    # merge order
    units: list[_GroupRun] = []
    unit_by_group: dict[int, _GroupRun] = {}
    for v, gs in per_variant:
        for g in gs:
            if not g.points:
                continue
            u = _GroupRun(
                variant=v, group=g, validate=validate,
                max_check_n=max_check_n, policy=policy, strict=strict,
                jr=jr, keys=keyed.get(id(g)),
            )
            units.append(u)
            unit_by_group[id(g)] = u

    t_run0 = time.perf_counter()
    stage_intervals = exec_backend.execute(units, strict)

    for v, gs in per_variant:
        indexed: list[tuple[int, PlanRow]] = []
        if jr is not None:
            for g in gs:
                indexed.extend(replayed.get(id(g), []))
        for g in gs:
            u = unit_by_group.get(id(g))
            if u is None:
                continue
            report.demotions.extend(u.demotions)
            report.failures.extend(u.failures)
            indexed.extend(u.rows)
        # emit in plan order regardless of how grouping reordered work
        report.rows.extend(
            row for _, row in sorted(indexed, key=lambda t: t[0]))

    measure_intervals = [u.measure_interval for u in units
                         if u.measure_interval is not None]
    report.executor = {
        "backend": exec_backend.name,
        "workers": int(exec_backend.workers),
        "groups": len(units),
        "stage_seconds": sum(b - a for a, b in stage_intervals),
        "measure_seconds": sum(b - a for a, b in measure_intervals),
        "stage_wall_seconds": (
            max(b for _, b in stage_intervals)
            - min(a for a, _ in stage_intervals)
        ) if stage_intervals else 0.0,
        "first_measure_seconds": (
            min(a for a, _ in measure_intervals) - t_run0
        ) if measure_intervals else 0.0,
        "staging_overlap_seconds": _overlap_seconds(stage_intervals,
                                                    measure_intervals),
        "wall_seconds": time.perf_counter() - t_run0,
    }
    return report
