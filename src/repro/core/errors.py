"""Failure taxonomy for fault-isolated sweep execution.

Every fault a sweep can hit is classified by *pipeline stage* so the
engine can decide what to do with it: demote the group's config (a
``CompileFailure`` on the parametric path often vanishes per-size
specialized), retry with backoff (``MeasureFailure`` under transient
load), or refuse up front (``CapacityRefused`` instead of an OOM kill).
``FailureRecord`` is the counterpart to :class:`repro.core.measure.Record`
— a failed plan point produces one, carrying enough pattern/schedule/env
context to diagnose the fault from the record alone.

The retry/backoff + straggler-watchdog policy shapes mirror
``runtime/fault_tolerance.py`` (the seed's training-loop harness); here
they guard individual measurements and driver groups instead of steps.
"""
from __future__ import annotations

import dataclasses
import json
import os

__all__ = [
    "BenchFailure",
    "LowerFailure",
    "CompileFailure",
    "ValidateFailure",
    "MeasureFailure",
    "BudgetExceeded",
    "CapacityRefused",
    "SweepFailures",
    "FailureRecord",
    "Demotion",
    "ResiliencePolicy",
    "classify_failure",
    "available_memory_bytes",
    "device_memory_bytes",
    "default_capacity_budget",
]


class BenchFailure(RuntimeError):
    """Base of the taxonomy.

    ``stage`` names the pipeline stage that faulted (lower / compile /
    validate / measure / capacity); ``transient`` marks faults worth a
    bounded retry before demotion; ``context`` holds the diagnosable
    payload (pattern, schedule, backend, env, ...); ``cause`` is the
    original exception when this wraps one.
    """

    stage = "unknown"
    transient = False

    def __init__(self, message: str, *, context: dict | None = None,
                 cause: BaseException | None = None):
        super().__init__(message)
        self.context: dict = dict(context or {})
        self.cause = cause


class LowerFailure(BenchFailure):
    """Pattern construction or jaxpr/StableHLO lowering faulted."""

    stage = "lower"


class CompileFailure(BenchFailure):
    """XLA refused or crashed compiling a lowered program."""

    stage = "compile"


class ValidateFailure(BenchFailure):
    """Executable output disagreed with the serial oracle."""

    stage = "validate"


class MeasureFailure(BenchFailure):
    """The timed run itself faulted; often transient (load spikes)."""

    stage = "measure"
    transient = True


class BudgetExceeded(MeasureFailure):
    """The straggler watchdog aborted a measurement over its wall-clock
    budget. Transient by inheritance: a retry under calmer load may fit."""

    stage = "measure"


class CapacityRefused(BenchFailure):
    """Working-set pre-flight refused an allocation exceeding the
    available-memory budget — a structured refusal instead of an OOM
    kill. Not transient (the point is simply too big), but demotion
    parametric→specialized shrinks the allocation env for the *other*
    rungs sharing the executable."""

    stage = "capacity"


class SweepFailures(BenchFailure):
    """Aggregate raised by strict callers of a fault-isolated report
    (``RunReport.raise_if_failed``). Carries the individual
    ``FailureRecord`` entries on ``.failures``."""

    stage = "sweep"

    def __init__(self, failures):
        self.failures = tuple(failures)
        brief = ", ".join(
            f"{f.variant}/{f.label} [{f.stage}:{f.error}]" for f in self.failures[:4])
        more = "" if len(self.failures) <= 4 else f" (+{len(self.failures) - 4} more)"
        super().__init__(
            f"{len(self.failures)} plan point(s) failed: {brief}{more}")


def classify_failure(exc: BaseException, stage: str, **context) -> BenchFailure:
    """Wrap ``exc`` into the taxonomy. An existing ``BenchFailure``
    passes through (its own stage wins) with ``context`` merged in;
    anything else becomes the class matching ``stage``."""
    if isinstance(exc, BenchFailure):
        for k, v in context.items():
            exc.context.setdefault(k, v)
        return exc
    cls = {
        "lower": LowerFailure,
        "compile": CompileFailure,
        "validate": ValidateFailure,
        "measure": MeasureFailure,
        "capacity": CapacityRefused,
    }.get(stage, MeasureFailure)
    return cls(f"{type(exc).__name__}: {exc}", context=context, cause=exc)


@dataclasses.dataclass
class FailureRecord:
    """One failed plan point — the ``Record`` counterpart.

    ``error`` is the taxonomy class name; the original exception class
    lands in ``context["cause"]``. ``demotions`` lists the ladder steps
    that were attempted before the point was marked failed."""

    variant: str
    label: str
    stage: str
    error: str
    message: str
    pattern: str = ""
    template: str = ""
    schedule: str = ""
    backend: str = ""
    env: dict = dataclasses.field(default_factory=dict)
    axis_point: dict = dataclasses.field(default_factory=dict)
    context: dict = dataclasses.field(default_factory=dict)
    attempts: int = 1
    demotions: list = dataclasses.field(default_factory=list)

    def __post_init__(self):
        self.demotions = list(self.demotions)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        # Context can hold arbitrary objects (envs, exceptions); keep the
        # record JSON-serializable no matter what landed in there.
        d["context"] = json.loads(json.dumps(d["context"], default=str))
        d["env"] = json.loads(json.dumps(d["env"], default=str))
        return d

    def json(self) -> str:
        return json.dumps(self.as_dict())


@dataclasses.dataclass(frozen=True)
class Demotion:
    """One demotion-ladder step taken for a driver group."""

    variant: str
    labels: tuple
    step: str       # e.g. "strided->gather", "parametric->specialized"
    stage: str      # stage of the failure that triggered the step
    error: str


@dataclasses.dataclass(frozen=True)
class ResiliencePolicy:
    """Bounded retry/backoff + demotion policy for ``run_plan``.

    Same shape as ``runtime.fault_tolerance.FTConfig``: transient faults
    get ``max_retries`` retries with exponential backoff before the
    group walks one demotion-ladder step."""

    max_retries: int = 1
    backoff_s: float = 0.05
    demote: bool = True


def available_memory_bytes() -> int | None:
    """``MemAvailable`` from /proc/meminfo, or None where unreadable."""
    try:
        with open("/proc/meminfo") as f:
            for line in f:
                if line.startswith("MemAvailable:"):
                    return int(line.split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        pass
    return None


def device_memory_bytes() -> int | None:
    """Memory the default device can hold (``memory_stats()
    ["bytes_limit"]``), or None where the backend reports none."""
    import jax

    stats = jax.devices()[0].memory_stats()
    return int(stats["bytes_limit"]) if stats and "bytes_limit" in stats \
        else None


def default_capacity_budget() -> int | None:
    """Capacity budget for the working-set pre-flight, in bytes.

    ``REPRO_CAPACITY_BUDGET`` overrides (empty/0 disables the check).
    Otherwise 80% of the memory of the device the arrays live on: its
    ``bytes_limit`` on an accelerator, host ``MemAvailable`` on the CPU
    backend only. None when the device reports no limit."""
    import jax

    raw = os.environ.get("REPRO_CAPACITY_BUDGET")
    if raw is not None:
        raw = raw.strip()
        if not raw or raw == "0":
            return None
        try:
            return int(raw)
        except ValueError:
            return None
    if jax.default_backend() == "cpu":
        avail = available_memory_bytes()
    else:
        avail = device_memory_bytes()
    return int(avail * 0.8) if avail else None
