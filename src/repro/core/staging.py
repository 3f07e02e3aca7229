"""Staged lower -> compile -> execute pipeline with a translation cache.

AdaptMemBench's value is cheap exploration: express a pattern once, fork
many (schedule, template, working-set) variants, measure each. The naive
pipeline re-resolves access plans, re-traces, and re-jits for every
variant, so sweep wall time is dominated by Python lowering and XLA
compilation instead of the kernels being measured. This module makes the
stages explicit (the JaCe ``lower().compile()`` discipline):

``Lowered``
    Access plans resolved against a concrete environment; the backend
    ``step(arrays) -> arrays`` function is built but nothing is traced.

``Compiled``
    The repetition loop is traced and AOT-compiled into an XLA
    executable (``jax.jit(...).lower(avals).compile()``). Compile time
    and cost analysis come from this stage for free — measurement never
    pays a hidden recompile.

``TranslationCache``
    Both stages are memoized behind a keyed cache. Keys are structural
    fingerprints of (pattern, schedule, env, backend, template knobs),
    so identical tuples never lower or compile twice across
    ``Driver.run`` working-set loops, ``sweep`` variants, and repeated
    validation. A shared ``GLOBAL_CACHE`` is the default so independent
    drivers in one process pool their work.

``precompile``
    Compiles many staged variants concurrently. XLA's backend compile
    releases the GIL, so a small thread pool overlaps the compiles of a
    sweep's variants even though tracing stays serial.

Donation invariant: every *measurement* executable — ``ParamCompiled``
always, ``Compiled`` when built with ``donate=True`` (what
``Driver.prepare`` requests) — donates its array operands, so a call
consumes its input tuple instead of paying a buffer copy; the ``bind``
methods thread outputs into subsequent calls. A custom kernel that names
the spaces it writes (``PatternSpec.writes``) donates those alone: its
weights and positions are read-only operands, passed through unchanged. Donated executables hit
jax's persistent compile cache across processes like undonated ones.
"""
from __future__ import annotations

import dataclasses
import os
import threading
from collections import OrderedDict
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Any, Callable, Mapping, Sequence

import numpy as np

import jax

from . import spans
from .pattern import PatternSpec
from .schedule import Schedule

__all__ = [
    "Lowered",
    "Compiled",
    "ParamLowered",
    "ParamCompiled",
    "TranslationCache",
    "GLOBAL_CACHE",
    "stage_lower",
    "stage_lower_parametric",
    "precompile",
    "fingerprint_pattern",
    "fingerprint_schedule",
    "disk_cache_stats",
]


# ---------------------------------------------------------------------------
# Structural fingerprints (cache keys)
# ---------------------------------------------------------------------------


def _freeze_callable(fn: Callable) -> tuple:
    """Fingerprint a function by code identity + closure contents.

    Pattern factories rebuild specs per call, so ``combine``/``init``
    lambdas are fresh objects every time; what identifies them is their
    bytecode and the values they close over (``triad(scalar=2.0)`` and
    ``triad(scalar=3.0)`` must not collide).
    """
    if hasattr(fn, "func"):  # functools.partial
        return ("partial", _freeze(fn.func), _freeze(fn.args),
                _freeze(tuple(sorted(fn.keywords.items()))))
    code = getattr(fn, "__code__", None)
    if code is None:
        return ("obj", repr(fn))
    cells: tuple = ()
    if getattr(fn, "__closure__", None):
        cells = tuple(_freeze(c.cell_contents) for c in fn.__closure__)
    defaults = _freeze(fn.__defaults__) if fn.__defaults__ else ()
    return ("fn", fn.__module__, fn.__qualname__,
            hash(code.co_code), _freeze(code.co_consts), defaults, cells)


def _freeze(obj: Any) -> Any:
    """Recursively convert ``obj`` into a hashable structural key."""
    if obj is None or isinstance(obj, (bool, int, float, str, bytes)):
        return obj
    if isinstance(obj, (np.integer, np.floating)):
        return ("np", str(obj.dtype), obj.item())
    if isinstance(obj, np.ndarray):
        return ("nd", obj.shape, str(obj.dtype), hash(obj.tobytes()))
    if isinstance(obj, (tuple, list)):
        return tuple(_freeze(x) for x in obj)
    if isinstance(obj, dict):
        return tuple(sorted((str(k), _freeze(v)) for k, v in obj.items()))
    if isinstance(obj, PatternSpec):
        # specs captured in closures (mix components) must freeze
        # *structurally* — the frozen-dataclass hash below would compare
        # their lambdas by identity, splitting equal factory rebuilds
        return fingerprint_pattern(obj)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        try:
            hash(obj)
            return obj  # frozen dataclass (Affine, Dim, ...) — already a key
        except TypeError:
            return tuple(
                (f.name, _freeze(getattr(obj, f.name)))
                for f in dataclasses.fields(obj)
            )
    if callable(obj):
        return _freeze_callable(obj)
    return ("repr", repr(obj))


def fingerprint_pattern(pattern: PatternSpec) -> tuple:
    """Hashable structural identity of a PatternSpec.

    Two factory-built specs with equal structure (spaces, accesses,
    combine code + captured constants, domain) get equal fingerprints
    even though every Python object in them is fresh.
    """
    stmt = pattern.statement
    return (
        "pat",
        pattern.name,
        tuple(
            (s.name, _freeze(s.shape), s.dtype, _freeze(s.init))
            for s in pattern.spaces
        ),
        tuple((a.space, _freeze(a.resolved())) for a in stmt.reads),
        (stmt.write.space, _freeze(stmt.write.resolved())),
        _freeze(stmt.combine),
        pattern.domain.dims,
        pattern.flops_per_point,
        _freeze(pattern.kernel),
        _freeze(pattern.oracle),
        _freeze(pattern.derived),
        _freeze(pattern.trace),
        _freeze(pattern.mix),
        pattern.writes,
    )


def fingerprint_schedule(schedule: Schedule) -> tuple:
    return ("sch", schedule.name, schedule.transforms)


def _env_key(env: Mapping[str, int]) -> tuple:
    return tuple(sorted((str(k), int(v)) for k, v in env.items()))


# ---------------------------------------------------------------------------
# jax disk compilation cache accounting (the cross-process leg)
# ---------------------------------------------------------------------------
#
# jax's persistent compilation cache reports activity only through
# monitoring events; a process-wide listener folds them into counters so
# ``TranslationCache.stats()`` can report disk hits/misses alongside the
# in-process lower/compile accounting (and the smoke ledger records both).

_DISK_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
_disk_counters = {"hits": 0, "misses": 0}
# jax fires monitoring events from whichever thread ran the compile —
# under the engine's ThreadPoolBackend that is many threads at once, and
# an unlocked `+=` on the shared dict would drop counts
_disk_lock = threading.Lock()
_disk_listener_installed = False


def _install_disk_listener() -> None:
    global _disk_listener_installed
    if _disk_listener_installed:
        return
    _disk_listener_installed = True

    def _on_event(event, **kwargs):
        key = _DISK_EVENTS.get(event)
        if key is not None:
            with _disk_lock:
                _disk_counters[key] += 1

    jax.monitoring.register_event_listener(_on_event)


def enable_persistent_cache(default_dir: str) -> str:
    """Turn on jax's persistent compile cache for every compile, however
    fast or small. ``JAX_COMPILATION_CACHE_DIR``, where set, places it
    (jax reads that variable itself, and no other directory is set);
    otherwise ``default_dir``, a fixed path — the path is part of the
    cache's identity, so a moving directory never hits. Returns the
    directory in use."""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = default_dir
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir


def disk_cache_stats() -> dict:
    """jax persistent-cache counters for this process (0/0 when the disk
    cache is disabled — events never fire)."""
    from jax._src import compilation_cache as _cc

    with _disk_lock:
        return {
            "enabled": bool(_cc.is_persistent_cache_enabled()),
            "hits": _disk_counters["hits"],
            "misses": _disk_counters["misses"],
        }


_install_disk_listener()


# ---------------------------------------------------------------------------
# Staged artifacts
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Lowered:
    """Stage 1: access plans resolved, backend step built (nothing traced)."""

    pattern: PatternSpec
    schedule: Schedule
    env: dict
    backend: str
    step: Callable[[dict], dict]
    nest: Any                       # LoweredNest
    key: tuple | None               # None = uncacheable (fingerprint failed)
    lower_seconds: float
    cache: "TranslationCache | None" = None
    pallas_mode: str = ""           # "compiled"/"interpret" (pallas backend)

    @property
    def space_names(self) -> tuple[str, ...]:
        return tuple(sorted(s.name for s in self.pattern.spaces))

    def avals(self) -> tuple[jax.ShapeDtypeStruct, ...]:
        by_name = {s.name: s for s in self.pattern.spaces}
        return tuple(
            jax.ShapeDtypeStruct(
                by_name[nm].concrete_shape(self.env), np.dtype(by_name[nm].dtype)
            )
            for nm in self.space_names
        )

    def compile(self, *, ntimes: int, sync_every_rep: bool = False,
                donate: bool = False,
                cache: "TranslationCache | None" = None) -> "Compiled":
        """Stage 2: trace + AOT-compile the ``ntimes``-sweep repetition loop.

        ``donate=True`` donates the array operands (no per-call buffer
        copy — the measurement-loop mode ``Driver.prepare`` requests);
        donated executables consume their input tuple, so callers must
        go through :meth:`Compiled.bind` to thread outputs into
        subsequent calls. The flag is part of the cache key: a donated
        executable never masquerades as the re-callable one.
        """
        cache = cache or self.cache
        key = None
        if self.key is not None:
            key = ("exec", self.key, int(ntimes), bool(sync_every_rep),
                   bool(donate))
        out, hit = _compile_span(
            cache, key,
            lambda: _build_compiled(self, ntimes, sync_every_rep, donate))
        # per-caller view: never mutate the shared cached object (racy
        # under precompile threads and wrong for duplicate points)
        return dataclasses.replace(out, from_cache=hit) if hit else out


@dataclasses.dataclass
class Compiled:
    """Stage 3 handle: an executable repetition loop + its cost metadata.

    When ``donated`` is True the array operands are donated: a call
    consumes its input tuple in place of paying a working-set-sized
    buffer copy (the same economics as the parametric executables —
    copy-free on both sides of a strided-vs-specialized comparison).
    Donated handles must be driven through :meth:`bind`, which threads
    each call's output tuple into the next; calling ``run`` twice with
    the same tuple raises inside jax (the buffers are gone)."""

    lowered: Lowered
    names: tuple[str, ...]
    run: Callable                   # run(tup) -> tup, ntimes sweeps
    executable: Any                 # jax AOT executable (cost_analysis source)
    ntimes: int
    sync_every_rep: bool
    compile_seconds: float
    from_cache: bool = False
    donated: bool = False

    def __call__(self, tup):
        return self.run(tup)

    def bind(self) -> Callable:
        """A ``fn(tup) -> tup`` for the measurement loop.

        Undonated executables are re-callable as-is. Donated ones get
        the same buffer-threading wrapper as
        :meth:`ParamCompiled.bind`: repeated calls (the timing loop)
        feed each call's output tuple into the next, so the caller's
        seed tuple is only consumed once — and a *different* tuple
        passed later raises instead of being silently ignored.

        With spans on (``spans.enable()`` before this call) each call
        runs inside a ``repro.dispatch`` span; with them off the loop
        gets the plain callable, decided here and not per call."""
        run = spans.wrap(self.run, "repro.dispatch",
                         n=self.lowered.env.get("n"))
        if not self.donated:
            return run
        state: dict = {}

        def fn(tup):
            if "tup" in state:
                if tup is not state["seed"] and tup is not state["tup"]:
                    raise ValueError(
                        "donated executable already threads its buffers; "
                        "a new input tuple would be ignored — call bind() "
                        "again for a fresh stream"
                    )
                tup = state["tup"]
            else:
                state["seed"] = tup
            out = run(tup)
            state["tup"] = out
            return out

        return fn

    def cost_analysis(self) -> dict:
        ca = self.executable.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return ca


def _build_compiled(lowered: Lowered, ntimes: int,
                    sync_every_rep: bool, donate: bool = False) -> Compiled:
    """The executable of ``ntimes`` sweeps. It takes the spaces the
    pattern writes (``PatternSpec.writes``; every space where None),
    donated when ``donate`` and returned, then any read-only ones
    (weights, positions), neither donated nor returned. ``run`` keeps
    the ``run(tup) -> tup`` contract over every space: the read-only
    arrays come back as they went in."""
    names = lowered.space_names
    step = lowered.step
    writes = lowered.pattern.writes
    own = names if writes is None else tuple(k for k in names if k in writes)
    rest = tuple(k for k in names if k not in own)

    def step_t(tup, ro=()):
        d = dict(zip(own, tup))
        d.update(zip(rest, ro))
        out = step(d)
        for k in rest:
            if out[k] is not d[k]:
                raise ValueError(
                    f"pattern {lowered.pattern.name!r} writes space {k!r}, "
                    "which its `writes` does not name")
        return tuple(out[k] for k in own)

    by_name = dict(zip(names, lowered.avals()))
    avals = (tuple(by_name[k] for k in own),)
    if rest:
        avals += (tuple(by_name[k] for k in rest),)
    compile_one = (_compile_donated if donate
                   else lambda fn, *a: jax.jit(fn).lower(*a).compile())
    if sync_every_rep:
        exe = compile_one(step_t, *avals)

        def run_own(tup, *ro):
            for _ in range(ntimes):
                tup = exe(tup, *ro)
                jax.block_until_ready(tup)
            return tup
    else:
        def fused(tup, *ro):
            return jax.lax.fori_loop(0, ntimes, lambda _, t: step_t(t, *ro),
                                     tup)

        exe = compile_one(fused, *avals)
        run_own = exe
    spans.note_executable(exe)
    if rest:
        at = {k: i for i, k in enumerate(names)}

        def run(tup):
            got = dict(zip(own, run_own(tuple(tup[at[k]] for k in own),
                                        tuple(tup[at[k]] for k in rest))))
            return tuple(got.get(k, tup[at[k]]) for k in names)
    else:
        run = run_own
    return Compiled(
        lowered=lowered, names=names, run=run, executable=exe,
        ntimes=ntimes, sync_every_rep=sync_every_rep,
        compile_seconds=0.0, donated=donate,
    )


# ---------------------------------------------------------------------------
# Parametric staged artifacts (one executable per ladder)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ParamLowered:
    """Stage 1, shape-polymorphic: the working-set parameter(s) stay
    symbolic. ``step(arrays, pvals)`` takes capacity-shaped arrays plus
    one traced int32 scalar per parameter; a whole ladder shares this
    artifact (and the one executable compiled from it)."""

    pattern: PatternSpec
    schedule: Schedule
    cap_env: dict                   # capacity env (arrays allocated here)
    params: tuple[str, ...]
    backend: str
    step: Callable[[dict, tuple], dict]
    pnest: Any                      # ParamNest
    key: tuple | None
    lower_seconds: float
    # which lowering regime the step was built with: "strided" (dynamic-
    # slice windows, per-call cost matching the specialized path) or
    # "gather" (masked gather/scatter fallback)
    param_path: str = "gather"
    # how many dynamic bands the strided windows span (1 = lane windows,
    # 2/3 = the stencil (i x j[, k]) boxes; 0 on the gather path)
    param_window_rank: int = 0
    cache: "TranslationCache | None" = None
    pallas_mode: str = ""           # "compiled"/"interpret" (pallas backend)

    # Driver.run treats lowered.env as the allocation env; for the
    # parametric artifact that is the capacity env.
    @property
    def env(self) -> dict:
        return self.cap_env

    @property
    def param_names(self) -> tuple[str, ...]:
        return self.params

    @property
    def space_names(self) -> tuple[str, ...]:
        return tuple(sorted(s.name for s in self.pattern.spaces))

    def avals(self) -> tuple:
        by_name = {s.name: s for s in self.pattern.spaces}
        arr = tuple(
            jax.ShapeDtypeStruct(
                by_name[nm].concrete_shape(self.cap_env),
                np.dtype(by_name[nm].dtype),
            )
            for nm in self.space_names
        )
        pv = tuple(
            jax.ShapeDtypeStruct((), np.dtype(np.int32)) for _ in self.params
        )
        return arr, pv

    def compile(self, *, ntimes: int, sync_every_rep: bool = False,
                cache: "TranslationCache | None" = None) -> "ParamCompiled":
        cache = cache or self.cache
        key = None
        if self.key is not None:
            key = ("pexec", self.key, int(ntimes), bool(sync_every_rep))
        out, hit = _compile_span(
            cache, key,
            lambda: _build_param_compiled(self, ntimes, sync_every_rep))
        return dataclasses.replace(out, from_cache=hit) if hit else out


@dataclasses.dataclass
class ParamCompiled:
    """One executable repetition loop shared by a whole working-set
    ladder: ``run(tup, pvals)`` executes ``ntimes`` sweeps at the working
    set named by the ``pvals`` scalars.

    The array operands are **donated**: without donation every call pays
    a capacity-sized buffer copy (the executable's shapes are the
    ladder's capacity, not the rung), which is exactly the
    pattern-independent overhead the strided regime exists to avoid.
    Consequence: a ``tup`` passed to ``run`` is consumed — reuse the
    *returned* tuple instead (:meth:`bind` does this threading for the
    measurement loop automatically)."""

    lowered: ParamLowered
    names: tuple[str, ...]
    run: Callable
    executable: Any
    ntimes: int
    sync_every_rep: bool
    compile_seconds: float
    from_cache: bool = False

    @property
    def param_names(self) -> tuple[str, ...]:
        return self.lowered.params

    @property
    def param_path(self) -> str:
        """Lowering regime of the shared executable ("strided"/"gather")."""
        return self.lowered.param_path

    @property
    def param_window_rank(self) -> int:
        """Window dimensionality of the strided regime (0 on gather)."""
        return self.lowered.param_window_rank

    def __call__(self, tup, pvals):
        return self.run(tup, pvals)

    def bind(self, env: Mapping[str, int]) -> Callable:
        """Close over one ladder point: returns ``fn(tup) -> tup``.

        The wrapper threads the donated buffers: repeated calls (the
        timing loop) feed each call's output tuple into the next, so the
        caller's original ``tup`` is only consumed once — which means a
        *different* tuple passed to a later call would be silently
        ignored. That is a measurement-loop contract (the loop re-passes
        the same seed tuple every rep), so passing anything else raises
        instead of computing on stale state.

        With spans on (``spans.enable()`` before this call) each call
        runs inside a ``repro.dispatch`` span; with them off the loop
        gets the plain callable, decided here and not per call."""
        pvals = tuple(np.int32(env[p]) for p in self.param_names)
        run = spans.wrap(self.run, "repro.dispatch", n=env.get("n"))
        state: dict = {}

        def fn(tup):
            if "tup" in state:
                if tup is not state["seed"] and tup is not state["tup"]:
                    raise ValueError(
                        "bound parametric executable already threads its "
                        "donated buffers; a new input tuple would be "
                        "ignored — call bind() again for a fresh stream"
                    )
                tup = state["tup"]
            else:
                state["seed"] = tup
            out = run(tup, pvals)
            state["tup"] = out
            return out

        return fn

    def cost_analysis(self) -> dict:
        ca = self.executable.cost_analysis() or {}
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        return ca


def _compile_donated(fn, *aval_groups):
    """AOT-compile ``fn`` with its array tuple donated. Donated
    executables share jax's persistent cache like any other: the module
    name is the function's own, so a cold process finds the executable a
    previous one compiled."""
    return jax.jit(fn, donate_argnums=(0,)).lower(*aval_groups).compile()


def _build_param_compiled(lowered: ParamLowered, ntimes: int,
                          sync_every_rep: bool) -> ParamCompiled:
    names = lowered.space_names
    step = lowered.step

    def step_t(tup, pvals):
        d = dict(zip(names, tup))
        d = step(d, pvals)
        return tuple(d[k] for k in names)

    avals, pavals = lowered.avals()
    # donate the array operands: undonated calls copy the full
    # capacity-shaped buffers on every invocation, a cost proportional to
    # the ladder *capacity* rather than the rung being measured
    if sync_every_rep:
        exe = _compile_donated(step_t, avals, pavals)

        def run(tup, pvals):
            for _ in range(ntimes):
                tup = exe(tup, pvals)
                jax.block_until_ready(tup)
            return tup
    else:
        def fused(tup, pvals):
            return jax.lax.fori_loop(
                0, ntimes, lambda _, t: step_t(t, pvals), tup
            )

        exe = _compile_donated(fused, avals, pavals)
        run = exe
    spans.note_executable(exe)
    return ParamCompiled(
        lowered=lowered, names=names, run=run, executable=exe,
        ntimes=ntimes, sync_every_rep=sync_every_rep,
        compile_seconds=0.0,
    )


# ---------------------------------------------------------------------------
# Translation cache
# ---------------------------------------------------------------------------


class TranslationCache:
    """Keyed LRU memo for both pipeline stages, with hit/miss accounting.

    Thread-safe for concurrent ``precompile`` workers: lookups and
    insertions are locked; builders run outside the lock, and
    concurrent requests for one key deduplicate onto a single in-
    flight build (waiters count as hits — they paid a wait, not a
    compile).

    ``capacity`` bounds each stage's store: multi-axis plan grids
    (config × pattern × env points) would otherwise pin executables
    without limit in a long-lived exploration process. The least
    recently *used* entry is evicted (a grid re-run in plan order keeps
    its warm tail); evictions are counted in :meth:`stats`. Default:
    :data:`DEFAULT_CAPACITY` per stage, overridable per instance or —
    for the process-wide ``GLOBAL_CACHE`` — via ``REPRO_CACHE_CAPACITY``.
    """

    DEFAULT_CAPACITY = 1024

    def __init__(self, capacity: int | None = None) -> None:
        if capacity is None:
            capacity = self.DEFAULT_CAPACITY
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._lowered: "OrderedDict[tuple, Lowered]" = OrderedDict()
        self._compiled: "OrderedDict[tuple, Compiled]" = OrderedDict()
        self._inflight: dict[tuple, Future] = {}
        self._validated: set[tuple] = set()
        self.lower_hits = 0
        self.lower_misses = 0
        self.compile_hits = 0
        self.compile_misses = 0
        self.evictions = 0        # LRU executable/lowering evictions
        self.validated_drops = 0  # validated-memo clears (separate event)

    def _get_or_build(self, store: "OrderedDict", key, builder,
                      kind: str) -> tuple[Any, bool]:
        with self._lock:
            hit = store.get(key)
            if hit is not None:
                store.move_to_end(key)
                setattr(self, f"{kind}_hits", getattr(self, f"{kind}_hits") + 1)
                return hit, True
            fut = self._inflight.get(key)
            if fut is None:
                fut = Future()
                self._inflight[key] = fut
                owner = True
                setattr(self, f"{kind}_misses",
                        getattr(self, f"{kind}_misses") + 1)
            else:
                owner = False
                setattr(self, f"{kind}_hits", getattr(self, f"{kind}_hits") + 1)
        if not owner:
            return fut.result(), True
        try:
            out = builder()
        except BaseException as e:
            with self._lock:
                self._inflight.pop(key, None)
            fut.set_exception(e)
            raise
        with self._lock:
            store[key] = out
            store.move_to_end(key)
            while len(store) > self.capacity:
                store.popitem(last=False)
                self.evictions += 1
            self._inflight.pop(key, None)
        fut.set_result(out)
        return out, False

    def _lowered_get_or_build(self, key, builder) -> tuple[Lowered, bool]:
        return self._get_or_build(self._lowered, key, builder, "lower")

    def _compiled_get_or_build(self, key, builder) -> tuple[Compiled, bool]:
        return self._get_or_build(self._compiled, key, builder, "compile")

    # -- validation memo (sweeps validate a variant once, not per set) ------

    def was_validated(self, key: tuple) -> bool:
        with self._lock:
            return key in self._validated

    def mark_validated(self, key: tuple) -> None:
        with self._lock:
            # bound the memo like the stage stores: re-validation is much
            # cheaper than a compile, so crossing the cap just drops the
            # set (no LRU bookkeeping on this path)
            if len(self._validated) >= 4 * self.capacity:
                self._validated.clear()
                self.validated_drops += 1
            self._validated.add(key)

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            total = (self.lower_hits + self.lower_misses
                     + self.compile_hits + self.compile_misses)
            hits = self.lower_hits + self.compile_hits
            return {
                "lower_hits": self.lower_hits,
                "lower_misses": self.lower_misses,
                "compile_hits": self.compile_hits,
                "compile_misses": self.compile_misses,
                "entries": len(self._lowered) + len(self._compiled),
                "capacity": self.capacity,
                "evictions": self.evictions,
                "validated_drops": self.validated_drops,
                "hit_rate": (hits / total) if total else 0.0,
                "disk": disk_cache_stats(),
            }

    def clear(self) -> None:
        with self._lock:
            self._lowered.clear()
            self._compiled.clear()
            self._validated.clear()
            self.lower_hits = self.lower_misses = 0
            self.compile_hits = self.compile_misses = 0
            self.evictions = 0
            self.validated_drops = 0


def _device_cache_key(device: int | None) -> int | None:
    """Cache-key form of a device-axis pin: the index wraps modulo the
    visible device count, mirroring how ``Driver._device`` resolves it,
    so collapsed plan indices (device 0 vs device 4 on a 4-device box)
    share one executable instead of compiling duplicates. ``None`` (no
    pin) stays a distinct key: an unpinned compile runs under the
    ambient default device, which a ``jax.default_device`` scope can
    point anywhere."""
    if device is None:
        return None
    return device % len(jax.devices())


def _global_capacity() -> int | None:
    raw = os.environ.get("REPRO_CACHE_CAPACITY", "")
    try:
        return int(raw) if raw else None
    except ValueError:  # pragma: no cover - operator typo
        return None


GLOBAL_CACHE = TranslationCache(capacity=_global_capacity())


# ---------------------------------------------------------------------------
# Spans around the cached stages
# ---------------------------------------------------------------------------


def _stamped(sp: spans.Span, build: Callable, field: str,
             attrs: Callable[[], dict]) -> Callable:
    """``build`` as a cache builder that ends ``sp`` when the artifact is
    built and stores the span's seconds in its ``field``: before the
    cache publishes it, so a concurrent waiter never copies it unset."""
    def builder():
        out = build()
        sp.attrs.update(attrs())
        sp.close()
        setattr(out, field, sp.seconds)
        return out
    return builder


def _lower_span(cache: "TranslationCache | None", key, build: Callable):
    """Stage 1 through the cache inside a ``repro.lower`` span (``cache``
    = hit/built); a built artifact's ``lower_seconds`` is the span's."""
    with spans.span("repro.lower") as sp:
        builder = _stamped(sp, build, "lower_seconds",
                           lambda: {"cache": "built"})
        if cache is None or key is None:
            return builder()
        out, hit = cache._lowered_get_or_build(key, builder)
        if hit:
            sp.attrs["cache"] = "hit"
    if out.cache is None:
        out.cache = cache
    return out


def _compile_span(cache: "TranslationCache | None", key, build: Callable):
    """Stage 2 through the cache inside a ``repro.compile`` span:
    ``source`` is memory (this cache), disk (jax's persistent cache, from
    its hit counter; process-wide, so a concurrent compile's hit can be
    credited here) or compiled. Returns ``(artifact, hit)``; a built
    artifact's ``compile_seconds`` is the span's."""
    with spans.span("repro.compile") as sp:
        disk0 = disk_cache_stats()["hits"]
        builder = _stamped(
            sp, build, "compile_seconds",
            lambda: {"source": "disk" if disk_cache_stats()["hits"] > disk0
                     else "compiled"})
        if cache is None or key is None:
            return builder(), False
        out, hit = cache._compiled_get_or_build(key, builder)
        if hit:
            sp.attrs["source"] = "memory"
    return out, hit


# ---------------------------------------------------------------------------
# Stage 1 entry point
# ---------------------------------------------------------------------------


def stage_lower(
    pattern: PatternSpec, schedule: Schedule, env: Mapping[str, int],
    backend: str = "jax", *, grid_bands: tuple[str, ...] | None = None,
    force_gather: bool = False, device: int | None = None,
    cache: TranslationCache | None = None,
) -> Lowered:
    """Resolve access plans and build the backend step, through the cache.

    ``device`` is the caller's device-axis pin (an index into
    ``jax.devices()``); it is part of the cache key because an AOT
    executable is bound to the device it compiled on — an artifact built
    for device 0 must never be replayed as device 3's. The key holds
    the *wrapped* index (modulo the visible device count, exactly how
    the driver resolves the pin), so plan indices that collapse onto
    one physical device share one executable.
    """
    from . import codegen  # deferred: codegen imports nothing from here

    env = dict(env)
    # the resolved execution mode is part of a pallas artifact's identity:
    # a cache entry (or journal record) built under interpret must never
    # be mistaken for a natively compiled one on another platform
    pallas_mode = codegen.pallas_platform_mode() if backend == "pallas" else ""
    try:
        key = (
            "lower", fingerprint_pattern(pattern),
            fingerprint_schedule(schedule), backend, pallas_mode or None,
            tuple(grid_bands) if grid_bands else None,
            bool(force_gather), _device_cache_key(device), _env_key(env),
        )
    except (TypeError, ValueError, AttributeError):
        key = None  # unhashable pattern piece: bypass the cache

    def builder() -> Lowered:
        plan = codegen.plan_nest(pattern, schedule, env)
        if backend == "jax":
            step = codegen.lower_jax(
                pattern, schedule, env, force_gather=force_gather, plan=plan
            )
        elif backend == "pallas":
            step = codegen.lower_pallas(
                pattern, schedule, env, mode=pallas_mode,
                grid_bands=grid_bands, plan=plan,
            )
        else:
            raise ValueError(backend)
        return Lowered(
            pattern=pattern, schedule=schedule, env=env, backend=backend,
            step=step, nest=plan.nest, key=key,
            lower_seconds=0.0, cache=cache, pallas_mode=pallas_mode,
        )

    return _lower_span(cache, key, builder)


def stage_lower_parametric(
    pattern: PatternSpec, schedule: Schedule, cap_env: Mapping[str, int],
    params: tuple[str, ...] = ("n",), backend: str = "jax", *,
    param_path: str = "auto", chunk: "int | tuple | None" = None,
    assume_full: bool = False, device: int | None = None,
    cache: TranslationCache | None = None,
) -> ParamLowered:
    """Shape-polymorphic stage 1: keep ``params`` symbolic, through the
    cache. The key deliberately omits the per-point env — every ladder
    point maps onto one entry, which is the whole point — but it *does*
    fingerprint the requested ``param_path`` regime, so a forced-gather
    artifact never masquerades as the strided one (and vice versa).

    Raises :class:`~repro.core.schedule.SymbolicLowerError` when a
    transform genuinely needs concrete extents (or ``param_path=
    "strided"`` is requested for an ineligible nest); callers fall back
    to per-size :func:`stage_lower` specialization. The pallas backend
    supports the strided regime only (grid-mapped N-D windows); nests
    that would need the gather fallback raise ``SymbolicLowerError``
    the same way.
    """
    from . import codegen

    if backend not in ("jax", "pallas"):
        from .schedule import SymbolicLowerError

        raise SymbolicLowerError(
            f"parametric lowering targets the jax/pallas backends, "
            f"not {backend!r}"
        )
    cap_env = dict(cap_env)
    params = tuple(params)
    pallas_mode = codegen.pallas_platform_mode() if backend == "pallas" else ""
    if backend == "pallas" and param_path == "gather":
        from .schedule import SymbolicLowerError

        raise SymbolicLowerError(
            "the pallas parametric path has no gather regime; use "
            "param_path='strided' (or the jax backend)"
        )
    # chunk is either a lane-chunk int or an N-D ((band, C), ...) window
    # spec resolved by the ladder policy; both fingerprint into the key
    if chunk is not None and not isinstance(chunk, int):
        chunk = tuple((int(b), int(c)) for b, c in chunk)
    try:
        key = (
            "plower", fingerprint_pattern(pattern),
            fingerprint_schedule(schedule), backend, pallas_mode or None,
            params, str(param_path), chunk, bool(assume_full),
            _device_cache_key(device), _env_key(cap_env),
        )
    except (TypeError, ValueError, AttributeError):
        key = None  # unhashable pattern piece: bypass the cache

    def builder() -> ParamLowered:
        pnest = schedule.lower_symbolic(pattern.domain, params)
        kw = {} if chunk is None else {"chunk": chunk}
        if backend == "pallas":
            step = codegen.lower_pallas_parametric(
                pattern, schedule, cap_env, params=params, pnest=pnest,
                assume_full=assume_full, mode=pallas_mode, **kw,
            )
        else:
            step = codegen.lower_jax_parametric(
                pattern, schedule, cap_env, params=params, pnest=pnest,
                param_path=param_path, assume_full=assume_full, **kw,
            )
        return ParamLowered(
            pattern=pattern, schedule=schedule, cap_env=cap_env,
            params=params, backend=backend, step=step, pnest=pnest,
            key=key, lower_seconds=0.0,
            param_path=getattr(step, "param_path", "gather"),
            param_window_rank=getattr(step, "param_window_rank", 0),
            cache=cache, pallas_mode=pallas_mode,
        )

    return _lower_span(cache, key, builder)


# ---------------------------------------------------------------------------
# Concurrent compile
# ---------------------------------------------------------------------------


def precompile(thunks: Sequence[Callable[[], Any]],
               max_workers: int | None = None) -> list:
    """Run compile thunks concurrently; returns their results in order.

    XLA's ``backend_compile`` releases the GIL, so a small pool overlaps
    the per-variant compiles of a sweep. Tracing inside each thunk stays
    correct (JAX trace state is thread-local) but serializes on the GIL;
    the win is the backend compile, which dominates.
    """
    thunks = list(thunks)
    if len(thunks) <= 1:
        return [t() for t in thunks]
    if max_workers is None:
        max_workers = min(4, len(thunks), os.cpu_count() or 1)
    if max_workers <= 1:
        return [t() for t in thunks]
    thunks = [spans.carry(t) for t in thunks]
    with ThreadPoolExecutor(max_workers=max_workers) as ex:
        return list(ex.map(lambda t: t(), thunks))


def pipeline_compile(lower_thunks: Sequence[Callable[[], Any]],
                     compile_fn: Callable[[Any], Any] | None = None,
                     max_workers: int | None = None) -> list:
    """Overlap serial lowering with concurrent compilation.

    Each ``lower_thunks[i]()`` runs on the calling thread (JAX tracing
    is GIL-bound, so serializing it costs nothing) and its result is
    immediately handed to a worker that runs ``compile_fn`` (default:
    ``lowered.compile()``), which spends its time in XLA with the GIL
    released. Total wall time approaches ``max(sum(lower), sum(compile)
    / workers)`` instead of their sum. Returns compiled results in
    order.
    """
    if compile_fn is None:
        compile_fn = lambda lowered: lowered.compile()
    lower_thunks = list(lower_thunks)
    if len(lower_thunks) <= 1:
        return [compile_fn(t()) for t in lower_thunks]
    if max_workers is None:
        max_workers = min(4, len(lower_thunks), os.cpu_count() or 1)
    if max_workers <= 1:
        return [compile_fn(t()) for t in lower_thunks]
    with ThreadPoolExecutor(max_workers=max_workers) as ex:
        futures = [ex.submit(compile_fn, t()) for t in lower_thunks]
        return [f.result() for f in futures]
