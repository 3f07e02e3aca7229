"""Measurement: timing, bandwidth accounting, and counter surrogates.

The paper's drivers wrap every kernel with (a) a repetition loop, (b)
timers, and (c) PAPI counters. On this target:

* timing — ``time_fn`` with ``block_until_ready`` fencing; medians over
  repeats. On the CPU container these are CPU numbers and records say so.
* achieved bandwidth — derived from the pattern's access list (bytes per
  iteration point x points x ntimes / seconds), the same accounting STREAM
  and the paper use (write-allocate traffic excluded, as in STREAM).
* counters — two surrogates for PAPI:
    - ``hlo_counters``: FLOPs / bytes-accessed from
      ``compiled.cost_analysis()`` (what the XLA:TPU compiler claims);
    - ``tile_traffic``: an analytic model of (8,128)-native-tile fetches
      and writebacks per program, the analogue of L1 line fills and
      requests-for-exclusive-access. It is exact for the affine patterns
      here, which is the point: the paper uses counters to *detect* false
      sharing; we can *prove* tile sharing from the schedule and report it
      in the same shape.
"""
from __future__ import annotations

import dataclasses
import json
import statistics
import time
from typing import Callable, Mapping, Sequence

import numpy as np

import jax

from .errors import BudgetExceeded

__all__ = [
    "TimingResult",
    "time_fn",
    "time_pair",
    "hlo_counters",
    "TileTraffic",
    "tile_traffic",
    "NATIVE_TILE",
    "Record",
    "latency_ns",
]

# TPU v5e native tile for f32 operands: 8 sublanes x 128 lanes. Narrower
# types pack more elements into the same 4 KiB ((16, 128) for bf16).
NATIVE_TILE = (8, 128)
NATIVE_TILE_BYTES = NATIVE_TILE[0] * NATIVE_TILE[1] * 4


def _cv(times: Sequence[float]) -> float:
    """Sample coefficient of variation; 0 for fewer than two samples."""
    if len(times) < 2:
        return 0.0
    mean = sum(times) / len(times)
    if mean <= 0:
        return 0.0
    var = sum((t - mean) ** 2 for t in times) / (len(times) - 1)
    return (var ** 0.5) / mean


@dataclasses.dataclass
class TimingResult:
    seconds: float          # median per-call wall time
    reps: int
    all_seconds: tuple[float, ...]   # chronological, unsorted
    # staged pipeline: AOT compile time, reported separately from run
    # time so sweep records never fold translation cost into bandwidth
    compile_seconds: float | None = None
    target_cv: float | None = None   # adaptive mode's convergence target
    converged: bool = True           # CV <= target within the rep budget
    slow_reps: int = 0               # reps flagged by the straggler check

    @property
    def minimum(self) -> float:
        """Fastest rep — the Mess-style noise-floor estimator (system
        noise only ever inflates a rep, never deflates it)."""
        return min(self.all_seconds) if self.all_seconds else self.seconds

    @property
    def cv(self) -> float:
        return _cv(self.all_seconds)

    def quality(self) -> dict:
        """The ``extra["timing_quality"]`` payload every Record stamps."""
        return {
            "median_s": self.seconds,
            "min_s": self.minimum,
            "cv": round(self.cv, 6),
            "reps": self.reps,
            "target_cv": self.target_cv,
            "converged": self.converged,
            "slow_reps": self.slow_reps,
        }


def time_fn(fn: Callable, *args, reps: int = 5, warmup: int = 2,
            compile_seconds: float | None = None,
            target_cv: float | None = None, max_reps: int | None = None,
            budget_s: float | None = None,
            straggler_factor: float = 3.0) -> TimingResult:
    """Median wall time of ``fn(*args)`` with device fencing.

    ``fn`` may be a pre-compiled executable from the staged pipeline
    (``staging.Compiled`` or a jax AOT executable); pass ``warmup=1``
    then — the first call only absorbs dispatch warm-up, compilation
    already happened — and thread its measured ``compile_seconds``
    through so records can report translation cost separately.

    Donated executables must arrive *bound* (``Compiled.bind()`` /
    ``ParamCompiled.bind(env)`` — what ``Prepared.executable()``
    returns): the timing loop re-passes the same seed tuple every rep,
    and the bound wrapper threads each call's output buffers into the
    next call, so the consumed donation stream stays valid.

    Adaptive quality mode: with ``target_cv`` set, keep adding reps past
    ``reps`` until the sample CV drops to the target or the rep budget
    (``max_reps``, default ``max(4*reps, 8)``) is spent; the result
    reports whether it ``converged``. Guard rails in any mode: a rep
    slower than ``straggler_factor`` x the trailing median (last 20
    reps) is counted in ``slow_reps`` — the ``FaultTolerantLoop``
    straggler policy applied to measurement; and with ``budget_s`` set,
    exceeding the wall-clock budget raises :class:`BudgetExceeded`
    (checked between reps — a single in-flight XLA call cannot be
    preempted, so the budget granularity is one rep).
    """
    t_start = time.perf_counter()

    def _check_budget(done: int, trailing: float | None) -> None:
        if budget_s is None:
            return
        elapsed = time.perf_counter() - t_start
        if elapsed > budget_s:
            raise BudgetExceeded(
                f"measurement exceeded its {budget_s:.3f}s wall-clock budget "
                f"after {elapsed:.3f}s ({done} reps timed)",
                context={"budget_s": budget_s, "elapsed_s": elapsed,
                         "reps_done": done, "trailing_median_s": trailing})

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
        _check_budget(0, None)
    cap = reps if target_cv is None else max(
        reps, max_reps if max_reps is not None else max(4 * reps, 8))
    times: list[float] = []
    slow = 0
    converged = True
    while True:
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        trailing = statistics.median(times[-20:]) if len(times) >= 3 else None
        if trailing is not None and straggler_factor \
                and dt > straggler_factor * trailing:
            slow += 1
        times.append(dt)
        _check_budget(len(times), trailing)
        if len(times) >= reps:
            if target_cv is None:
                break
            if _cv(times) <= target_cv:
                break
            if len(times) >= cap:
                converged = False
                break
    ordered = sorted(times)
    return TimingResult(ordered[len(ordered) // 2], len(times), tuple(times),
                        compile_seconds, target_cv, converged, slow)


def time_pair(fn_a: Callable, args_a: tuple, fn_b: Callable, args_b: tuple,
              *, reps: int = 7, passes: int = 1,
              warmup: int = 1) -> tuple[TimingResult, TimingResult]:
    """Matched-load interleaved A/B timing (the Mess discipline).

    Wall-clock on a shared machine is only comparable *under the same
    load*, so A and B are timed in strict alternation — every A rep has
    a B rep as its temporal neighbour, and a background-load spike hits
    both sides. Spikes can only inflate a rep, never deflate it, so
    consume the results via ``.minimum`` (min-of-reps) for ratio gates;
    ``.cv`` reports how noisy the session was. ``passes`` repeats the
    whole alternation block — callers wanting temporally *separated*
    passes (the PR-5 probe) call with ``passes=1`` from their own outer
    loop and fold the minima.

    Donated executables: same binding contract as :func:`time_fn`.
    """
    pairs = ((fn_a, args_a), (fn_b, args_b))
    for _ in range(warmup):
        for fn, args in pairs:
            jax.block_until_ready(fn(*args))
    times_a: list[float] = []
    times_b: list[float] = []
    for _ in range(passes):
        for _ in range(reps):
            for sink, (fn, args) in zip((times_a, times_b), pairs):
                t0 = time.perf_counter()
                jax.block_until_ready(fn(*args))
                sink.append(time.perf_counter() - t0)

    def _result(ts: list[float]) -> TimingResult:
        ordered = sorted(ts)
        return TimingResult(ordered[len(ordered) // 2], len(ts), tuple(ts))

    return _result(times_a), _result(times_b)


def hlo_counters(target, *args) -> dict[str, float]:
    """FLOPs and bytes-accessed as claimed by the compiled executable.

    ``target`` is either an already-compiled executable exposing
    ``cost_analysis()`` (``staging.Compiled`` / jax AOT executable — no
    recompile) or a jitted function, which is lowered and compiled here
    with ``*args``.
    """
    try:
        if hasattr(target, "cost_analysis"):
            ca = target.cost_analysis() or {}
        else:
            compiled = target.lower(*args).compile()
            ca = compiled.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return {
            "hlo_flops": float(ca.get("flops", float("nan"))),
            "hlo_bytes": float(
                sum(v for k, v in ca.items() if k.startswith("bytes accessed"))
            ),
        }
    except Exception as e:  # pragma: no cover - backend-specific
        return {"hlo_flops": float("nan"), "hlo_bytes": float("nan"),
                "hlo_error": str(e)}


# ---------------------------------------------------------------------------
# Analytic native-tile traffic (the PAPI surrogate)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class TileTraffic:
    """Per-sweep tile-granular traffic, split the way PAPI splits it.

    fetches            — tiles loaded across all programs (≈ L1 line fills)
    writebacks         — tiles written across all programs
    shared_write_tiles — tiles written by >1 program (the false-sharing
                         signal: each extra writer forces a read-modify-
                         write of a tile another program owns; on CPU this
                         is the request-for-exclusive-access storm)
    """

    fetches: int
    writebacks: int
    shared_write_tiles: int

    def as_dict(self) -> dict[str, int]:
        return dataclasses.asdict(self)


def _touched_tiles(lo: int, hi: int, tile_elems: int) -> set[int]:
    if hi <= lo:
        return set()
    return set(range(lo // tile_elems, (hi - 1) // tile_elems + 1))


def tile_traffic(
    *, spaces: Mapping[str, tuple[int, ...]],
    program_slices: Sequence[Mapping[str, tuple[int, int]]],
    written: str, itemsizes: Mapping[str, int] | None = None,
) -> TileTraffic:
    """Tile traffic for 1D-per-program slices (the paper's SMP studies).

    ``program_slices[p][space] = (lo, hi)`` is program p's contiguous
    element range in the *flattened* space. Tiles are NATIVE_TILE_BYTES
    blocks of the flat layout — the exact analogue of 64B cache lines.
    ``itemsizes[space]`` is the space's element size in bytes (4 where
    not given), so a bf16 tile holds twice the elements of an f32 one.
    """
    fetches = 0
    writebacks = 0
    writers: dict[tuple[str, int], int] = {}
    for sl in program_slices:
        for space, (lo, hi) in sl.items():
            size = (itemsizes or {}).get(space, 4)
            tiles = _touched_tiles(lo, hi, NATIVE_TILE_BYTES // size)
            fetches += len(tiles)
            if space == written:
                writebacks += len(tiles)
                for t in tiles:
                    writers[(space, t)] = writers.get((space, t), 0) + 1
    shared = sum(1 for v in writers.values() if v > 1)
    return TileTraffic(fetches, writebacks, shared)


# ---------------------------------------------------------------------------
# Output records (machine parsable + human readable, per the paper)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Record:
    pattern: str
    template: str
    schedule: str
    backend: str
    n: int
    working_set_bytes: int
    programs: int
    ntimes: int
    seconds: float
    gbs: float
    gflops: float
    level: str = ""            # which memory level the working set sits in
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def axis_point(self) -> dict:
        """The sweep-plan coordinates that produced this record (axis
        name -> labelled point), attached by the plan engine; empty for
        records measured outside a plan."""
        return dict(self.extra.get("axis_point", {}))

    def csv(self) -> str:
        us = self.seconds * 1e6
        return (
            f"{self.pattern}/{self.template}/{self.schedule}/{self.backend},"
            f"{us:.2f},{self.gbs:.3f}"
        )

    def json(self) -> str:
        return json.dumps(dataclasses.asdict(self))


def latency_ns(rec: "Record", accesses_per_point: int = 1) -> float:
    """Per-access time of a record in ns — the latency view of a
    measurement (``seconds`` covers ``ntimes`` sweeps of
    ``extra["points"]`` iteration points each). For serially-dependent
    patterns (pointer chase) this IS load-to-use latency; for throughput
    patterns it is the Mess-style time-per-access under the record's
    load point, paired with ``rec.gbs`` for bandwidth–latency curves.
    """
    pts = int(rec.extra.get("points", rec.n)) or 1
    return rec.seconds / (rec.ntimes * pts * accesses_per_point) * 1e9


def classify_level(working_set_bytes: int) -> str:
    """Bucket a working set by the v5e hierarchy (per-core view)."""
    if working_set_bytes <= 96 * 2 ** 10:          # fits VREG+small VMEM slice
        return "vreg"
    if working_set_bytes <= 64 * 2 ** 20:          # VMEM-resident half budget
        return "vmem"
    return "hbm"
