"""The benchmark's fixed loop: set-up, measured window, trace, check, result.

Everything that belongs to one configuration, one traffic mix or one
metric is a file of its own, found by the name ``BENCHMARK.json`` gives:

- ``configs/<config>.json`` — the configuration as it is run; its
  ``entry`` names ``entries/<entry>.py``, which stages the program's
  executables and makes their inputs on the device from the seed;
- ``configs/<config>.py`` — its plain reference (``reference``) and the
  bytes one call must move (``traffic_bytes``);
- ``traffic/<traffic>.json`` — the ladder of working sets and the calls
  per rung that make one pass;
- ``metrics/<metric>.py`` — a reader ``read(ctx)`` that returns the
  metric's value, or None where it finds nothing to read.

A run sets up (stage, inputs, one warm-up pass), measures passes over the
ladder for ``seconds``, dispatched a few seconds ahead of the pass it
waits for (``trace=1``: a shorter traced window instead),
reads the device's memory peak, checks what the timed calls produced
against the reference, and returns the result line as a dict.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import pathlib
import sys
import tempfile
import time
from collections import Counter, deque
from typing import Any, Callable

from . import trace as tracing

ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = "perfbench"
# the traced window: a few seconds of steady passes, traced in a run of
# its own, so the end-to-end runs carry no profiler cost
TRACE_SECONDS = 3.0
# the window dispatches this much work ahead of the pass it waits for,
# so the device keeps working through a stall of the host
LEAD_SECONDS = 4.0
SPAN = "pb."  # prefix of the benchmark's own host spans in a trace
# jax's own events for tracing, compiling and loading an executable
_COMPILE_EVENTS = ("/jax/core/compile/", "/jax/compilation_cache/cache_")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Call:
    """One call of a timed executable at one rung. ``run()`` dispatches it
    and returns what to block on; ``keep(out)`` lets the entry hold what
    its check compares; ``nbytes`` is the traffic one call must move."""

    label: str
    run: Callable[[], Any]
    keep: Callable[[Any], None]
    nbytes: dict


@dataclasses.dataclass
class Cell:
    """What an entry builds: the calls of one pass in ladder order, the
    seconds the program spent lowering and compiling them, the devices
    they run on, and ``check()``: ``[(name, value, limit), ...]`` of what
    the timed calls produced against the reference, plus the number of
    calls whose answer failed."""

    calls: list[Call]
    stage_s: float
    devices: list
    check: Callable[[], tuple[list[tuple[str, float, float]], int]]


@dataclasses.dataclass
class Window:
    seconds: float
    passes: list[float]
    calls: int
    nbytes: Counter


@dataclasses.dataclass
class Context:
    """What a metric reader sees."""

    setup_s: float
    stage_s: float
    window: Window
    trace: Any = None            # tracing.Trace of the traced window
    peaks: dict | None = None    # the device's row of peaks.json


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    """Import a file of the benchmark by its path (its name may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"no such benchmark file: {path}")
    name = "perfbench_" + "_".join(path.with_suffix("").parts[-2:])
    name = name.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(bench: dict, workload: str) -> tuple[dict, dict]:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return cell, configs[cell["config"]]


def metrics_for(bench: dict, workload: str, group: str) -> list[dict]:
    """The metrics of ``group`` (``end_to_end`` or ``per_layer``) that the
    cell reports: those that list it, and those that list no cells."""
    return [m for m in bench[group]
            if workload in m.get("workloads", [workload])]


def load_peaks(root: pathlib.Path, kind: str) -> dict:
    """The row of ``peaks.json`` for ``device_kind``. A kind missing from
    the table is an error, never a default."""
    table = load_json(root / HERE / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device_kind {kind!r} is not in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------


class CompileCounter:
    """Counts jax's tracing, compiling and cache-loading events while
    entered: a measured window must count none."""

    def __init__(self):
        self.count = 0

    def _on(self, event, *args, **kwargs):
        if event.startswith(_COMPILE_EVENTS):
            self.count += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_listener(self._on)
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_listener(self._on)
        jax.monitoring.unregister_event_duration_listener(self._on)


_FIRST: list = []  # the jitted marker, made on first use


def _marker(out):
    """One element of a call's first output: a tiny program queued after
    the call on its devices, which the host can wait on once the call's
    own outputs are donated into the next call."""
    import jax

    if not _FIRST:
        _FIRST.append(jax.jit(lambda x: x[(0,) * x.ndim]))
    return _FIRST[0](jax.tree.leaves(out)[0])


def lead_passes(pass_s: float) -> int:
    """How many passes the window dispatches ahead of the one it waits
    for: ``LEAD_SECONDS`` of work at the warm-up's pass time."""
    if pass_s <= 0:
        return 1
    return max(1, math.ceil(LEAD_SECONDS / pass_s))


def run_passes(calls: list[Call], reps: int, seconds: float,
               spans: bool = False, lead: int = 1) -> Window:
    """Passes over the ladder until ``seconds`` have gone by. A pass makes
    ``reps`` calls per rung, rung after rung, and ends in a marker of its
    last call (``_marker``). Passes are dispatched up to ``lead`` ahead of
    the one the host waits for, so that the device keeps working while
    the host stands still; the host waits on the markers in order, and a
    pass's time is the interval from the previous pass's completion to
    its own, as the host sees them. When the time is up nothing more is
    sent; the window closes when all that was sent has completed, and
    counts all of it. ``spans`` names the host's part of each call and
    wait in the profiler's trace."""
    import contextlib

    import jax

    span = (jax.profiler.TraceAnnotation if spans
            else lambda name: contextlib.nullcontext())
    passes: list[float] = []
    pending: deque = deque()
    sent = 0
    t0 = last = time.perf_counter()
    deadline = t0 + seconds

    def wait_oldest():
        nonlocal last
        with span(SPAN + "wait"):
            jax.block_until_ready(pending.popleft())
        now = time.perf_counter()
        passes.append(now - last)
        last = now

    while True:
        with span(SPAN + "pass"):
            for c in calls:
                for _ in range(reps):
                    with span(SPAN + "call:" + c.label):
                        out = c.run()
                    c.keep(out)
            pending.append(_marker(out))
        sent += 1
        while len(pending) > lead:
            wait_oldest()
        if time.perf_counter() >= deadline:
            break
    while pending:
        wait_oldest()
    nbytes: Counter = Counter()
    for c in calls:
        for k, v in c.nbytes.items():
            nbytes[k] += v * reps * sent
    return Window(seconds=last - t0, passes=passes,
                  calls=sent * reps * len(calls), nbytes=nbytes)


def warm_up(calls: list[Call], reps: int = 0) -> float:
    """Every timed executable and the pass marker once, outside the
    window; nothing kept. With ``reps``, then one whole pass, timed:
    returns its seconds (0.0 without)."""
    import jax

    out = None
    for c in calls:
        out = c.run()
        jax.block_until_ready(out)
    jax.block_until_ready(_marker(out))
    if not reps:
        return 0.0
    t0 = time.perf_counter()
    for c in calls:
        for _ in range(reps):
            out = c.run()
    jax.block_until_ready(_marker(out))
    return time.perf_counter() - t0


def traced_passes(calls: list[Call], reps: int, seconds: float,
                  lead: int = 1):
    """``run_passes`` under the profiler, into a temporary directory that
    is reduced and removed before this returns: (window, trace)."""
    import shutil

    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        t0 = time.perf_counter()
        jax.profiler.start_trace(tmp, profiler_options=opts)
        t1 = time.perf_counter()
        try:
            win = run_passes(calls, reps, seconds, spans=True, lead=lead)
        finally:
            t2 = time.perf_counter()
            jax.profiler.stop_trace()
        t3 = time.perf_counter()
        reduced = tracing.load(tmp, span_prefix=SPAN)
        print(f"trace: start {t1 - t0:.3f} s, stop "
              f"{time.perf_counter() - t2:.3f} s of which reduce "
              f"{time.perf_counter() - t3:.3f} s", file=sys.stderr)
        return win, reduced
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak(devices) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    return int(max(peaks, default=0))


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(chips: int) -> None:
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (jax.devices()[0] is "
                     f"{devs[0].platform!r}); this benchmark runs on the "
                     "chip only")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, {len(devs)} visible")


def prepare_program(root: pathlib.Path) -> str:
    """Put the program under test on the path and turn on its persistent
    compile cache: ``JAX_COMPILATION_CACHE_DIR`` where set, else the
    fixed ``experiments/.jax_cache`` in the checkout."""
    src = root / "src"
    if not (src / "repro").is_dir():
        raise FileNotFoundError(f"no program under test: {src / 'repro'} "
                                "is missing; run from a checkout of the "
                                "repository")
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    # libtpu logs to a fixed /tmp path unless told otherwise; a run
    # writes nothing outside its checkout and the caches it is given
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from repro.core.staging import enable_persistent_cache

    return enable_persistent_cache(str(root / "experiments" / ".jax_cache"))


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             root: pathlib.Path = ROOT, t_start: float | None = None,
             require_tpu: bool = True, peaks: dict | None = None) -> dict:
    """One run of one cell; returns the result line. ``require_tpu=False``
    and ``peaks`` (a peaks row) serve the rehearsal off the chip."""
    t_start = time.perf_counter() if t_start is None else t_start
    root = pathlib.Path(root)
    bench = load_json(root / "BENCHMARK.json")
    cell_entry, cfg_entry = find_cell(bench, workload)
    cfg = load_json(root / cfg_entry["file"])
    ref = load_module((root / cfg_entry["file"]).with_suffix(".py"))
    traffic = load_json(root / HERE / "traffic" / f"{cell_entry['traffic']}.json")
    group = "per_layer" if trace else "end_to_end"
    readers = {m["name"]: load_module(root / HERE / "metrics" / f"{m['name']}.py")
               for m in metrics_for(bench, workload, group)}
    entry = load_module(root / HERE / "entries" / f"{cfg['entry']}.py")

    prepare_program(root)
    if require_tpu:
        require_chips(int(cell_entry["chips"]))
    dev = device_info()
    if trace and peaks is None:
        peaks = load_peaks(root, dev["kind"])

    t_build = time.perf_counter()
    cell = entry.build(cfg, ref, traffic, seed)
    t_warm = time.perf_counter()
    reps = int(traffic["reps"])
    pass_s = warm_up(cell.calls, reps)
    lead = lead_passes(pass_s)
    print(f"setup: imports and devices {t_build - t_start:.3f} s, build "
          f"{t_warm - t_build:.3f} s (stage {cell.stage_s:.3f} s), warm-up "
          f"{time.perf_counter() - t_warm:.3f} s (a pass {pass_s:.4f} s, "
          f"{lead} dispatched ahead)", file=sys.stderr)
    with CompileCounter() as compiles:
        t_window = time.perf_counter()
        if trace:
            win, tr = traced_passes(cell.calls, reps,
                                    min(seconds, TRACE_SECONDS), lead)
        else:
            win, tr = run_passes(cell.calls, reps, seconds, lead=lead), None
    dev["memory_peak_bytes"] = memory_peak(cell.devices)

    t_check = time.perf_counter()
    checks, failed_rungs = cell.check()
    print(f"check: {time.perf_counter() - t_check:.3f} s", file=sys.stderr)
    checks.append(("compiles_in_window", compiles.count, 0))
    correct = all(v <= lim for _, v, lim in checks)
    ctx = Context(setup_s=t_window - t_start, stage_s=cell.stage_s,
                  window=win, trace=tr, peaks=peaks)
    metrics = {}
    for m in metrics_for(bench, workload, group):
        value = readers[m["name"]].read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": win.calls,
        "failed": win.calls * failed_rungs // len(cell.calls),
        "metrics": metrics,
        "device": dev,
    }
    if tr is not None:
        dev["busy_s"] = tracing.busy_s(tr)
        dev["window_s"] = win.seconds
        result["breakdown"] = tracing.breakdown(tr)
    result["check"] = {name: {"value": v, "limit": lim}
                       for name, v, lim in checks}
    return result


def report_checks(result: dict) -> None:
    """Each number compared beside its limit, as plain lines on stderr."""
    for name, c in result["check"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(f"correct = {str(result['correct']).lower()}", file=sys.stderr,
          flush=True)


def seed_key(seed: int):
    """A PRNG key from any whole seed: the low and the high 32 bits both
    count (``jax.random.key`` alone keeps only the low 32)."""
    import jax

    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
