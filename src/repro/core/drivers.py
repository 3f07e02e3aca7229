"""Benchmark driver templates — the kernel-independent layer.

The paper ships three driver templates; each has a direct analogue here:

* **Unified data spaces** (Listing 1): threads share one array through
  OpenMP work-sharing. Here: one array per data space; parallel "programs"
  are carved out of the iteration domain by tiling its outermost dim into
  ``programs`` contiguous chunks (exactly ``schedule(static, n/t)``). The
  chunks share native tiles at their seams — the false-sharing analogue.

* **Independent data spaces** (Listing 2): each thread owns a disjoint
  array. Here: every space gains a leading ``programs`` axis whose rows
  are optionally padded to the native tile (``pad`` elements), and the
  statement is rewritten to index through the program id — the exact
  transformation the paper performs in the memory-mapping macros
  (``A[t_id*8][i]``).

* **PAPI measurement** (template 3): ``measured=True`` attaches
  ``hlo_counters`` + analytic ``tile_traffic`` to every record.

A driver owns the repetition loop. ``sync_every_rep=False`` fuses all
``ntimes`` sweeps into one compiled ``lax.fori_loop`` — the ``nowait``
analogue (no host round-trip / no dispatch barrier between sweeps);
``True`` dispatches one sweep per call and fences, reproducing the
per-iteration barrier of Listing 1.

Measurement invariants: ``prepare``/``run`` stage through the
translation cache (identical tuples never lower or compile twice), the
executables they time are **donated** on the jax backend (no per-call
buffer copy on either the specialized or the parametric side — see
``staging``), ladders resolve one lowering regime up front
(``_resolve_param_path``: specialized / strided / gather, with the
exact per-env window-bounds check), and every record self-describes via
``extra`` (``param_path``, ``param_window_rank``, ``donated``,
``cache_hit``, ...).
"""
from __future__ import annotations

import contextlib
import dataclasses
from typing import Callable, Mapping, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from . import spans
from .codegen import serial_oracle
from .domain import Affine, Dim, IterDomain
from .errors import (
    BenchFailure,
    BudgetExceeded,
    CapacityRefused,
    CompileFailure,
    LowerFailure,
    default_capacity_budget,
)
from .measure import (
    Record,
    classify_level,
    hlo_counters,
    tile_traffic,
    time_fn,
)
from .pattern import Access, DataSpace, PatternSpec, Statement
from .schedule import Schedule, SymbolicLowerError, identity
from .staging import (
    GLOBAL_CACHE,
    Compiled,
    Lowered,
    ParamCompiled,
    ParamLowered,
    TranslationCache,
    fingerprint_pattern,
    precompile,
    stage_lower,
    stage_lower_parametric,
)

__all__ = [
    "DriverConfig",
    "Driver",
    "Prepared",
    "independent_view",
    "unified_program_schedule",
]


# ---------------------------------------------------------------------------
# Template transformations
# ---------------------------------------------------------------------------


def independent_view(pattern: PatternSpec, programs: int, pad: int = 0) -> PatternSpec:
    """Rewrite a pattern to the *independent data spaces* form.

    Every space of shape ``(n, ...)`` becomes ``(programs, n/programs + pad,
    ...)`` (the caller passes the *per-program* ``n`` in env — mirroring the
    paper's ``int N = n/t``); a new outermost iterator ``p`` runs over
    programs and all accesses are prefixed with it. ``pad`` is the paper's
    padding factor (8 doubles -> one 64B line; here pad to the 1024-element
    native tile with ``pad=tile-remainder`` or any nonzero slack).
    """
    p = "p"
    if p in pattern.domain.names:
        raise ValueError("pattern already has a 'p' iterator")
    if pattern.kernel is not None:
        raise ValueError(
            f"pattern {pattern.name!r} has a custom kernel; the independent "
            "template's access rewrite cannot apply to it (use unified with "
            "programs=1)"
        )

    def pad_shape(shape):
        first = Affine.of(shape[0]) + pad
        return (Affine.of(programs), first) + tuple(shape[1:])

    def pad_init(init):
        if not callable(init):
            return init
        # per-row init: drop the program grid, apply the original to the rest
        return lambda pgrid, *grids: init(*grids)

    spaces = tuple(
        dataclasses.replace(s, shape=pad_shape(s.shape), init=pad_init(s.init))
        for s in pattern.spaces
    )

    def prefix(acc: Access) -> Access:
        return Access(acc.space, (p,) + tuple(acc.index))

    stmt = Statement(
        reads=tuple(prefix(a) for a in pattern.statement.reads),
        write=prefix(pattern.statement.write),
        combine=pattern.statement.combine,
    )
    dom = IterDomain((Dim.of(p, 0, programs),) + pattern.domain.dims)
    return dataclasses.replace(
        pattern,
        name=f"{pattern.name}.indep{programs}" + (f".pad{pad}" if pad else ""),
        spaces=spaces,
        statement=stmt,
        domain=dom,
    )


def unified_program_schedule(
    pattern: PatternSpec, programs: int, env: Mapping[str, int],
    base: Schedule | None = None,
) -> Schedule:
    """Tile the outermost domain dim into ``programs`` chunks — the
    ``schedule(static, n/t)`` work-sharing split of the unified template."""
    sch = base or identity()
    if programs == 1:
        return sch  # no work-sharing split needed
    d0 = pattern.domain.dims[0]
    extent = d0.extent(env)
    if extent % programs != 0:
        raise ValueError(
            f"unified template needs programs | extent ({programs} vs {extent})"
        )
    # tile_by_count keeps the split affine in a symbolic extent (chunk
    # length n/programs becomes a rational coefficient), so the unified
    # template stays shape-polymorphic; concrete lowering is identical to
    # the old tile(extent // programs) form.
    return sch.tile_by_count(d0.name, programs, outer="prog", inner=d0.name)


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DriverConfig:
    template: str = "unified"       # unified | independent
    programs: int = 8               # "threads"
    pad: int = 0                    # independent-template row padding (elems)
    backend: str = "jax"            # jax | pallas
    schedule: Schedule | None = None  # extra transforms (applied to the kernel dims)
    ntimes: int = 50                # sweeps per measurement
    sync_every_rep: bool = False    # True = per-sweep barrier (Listing 1)
    reps: int = 5                   # timing repetitions (median)
    measured: bool = False          # attach counter surrogates (template 3)
    grid_bands: tuple[str, ...] | None = None  # pallas grid override
    validate_n: int | None = 64     # oracle-check size (None = skip)
    # Shape-polymorphic ladders: None = unset (specialize; the suite
    # runner may apply its workload-level policy); False = always
    # specialize per working set (one executable per n, never
    # overridden); "auto" = share one executable across the whole ladder
    # when the schedule lowers symbolically and every point satisfies
    # its divisibility constraints, else fall back; True = require the
    # parametric path (raise if unsupported).
    parametric: bool | str | None = None
    # Parametric lowering regime: "auto" prefers the strided fast path
    # (dynamic-slice windows — per-call cost matches the specialized
    # strided path) and falls back to masked gather/scatter; "strided"
    # requires the fast path (the ladder specializes — or raises under
    # parametric=True — when the nest is ineligible); "gather" pins the
    # masked form (the reference regime conformance tests pin down).
    # Records report the chosen regime as extra["param_path"]
    # ("specialized" when the point did not share an executable at all).
    param_path: str = "auto"
    # Buffer donation on the jax backend: None = backend default (jax
    # donates, pallas does not). False is the resilience engine's last
    # demotion rung — undonated executables copy per call but sidestep
    # any donation-stream fault. Parametric sharing requires donation,
    # so donate=False also forces the specialized path.
    donate: bool | None = None
    # Adaptive measurement quality (see measure.time_fn): repeat past
    # `reps` until the sample CV drops to target_cv, bounded by
    # max_reps. None keeps the fixed-rep legacy estimator.
    target_cv: float | None = None
    max_reps: int | None = None
    # Straggler watchdog: wall-clock budget per measurement point;
    # exceeding it raises BudgetExceeded (recorded as a failure by the
    # plan engine rather than hanging the sweep).
    time_budget_s: float | None = None
    # Working-set pre-flight: refuse (CapacityRefused) points whose
    # allocation would exceed this budget. None = process default
    # (REPRO_CAPACITY_BUDGET env var, else 80% of the device's memory).
    capacity_budget_bytes: int | None = None
    # Device pinning (the plan engine's device axis): an index into
    # jax.devices(), resolved modulo the device count so a plan written
    # for an 8-device mesh still runs (collapsed) on a smaller box.
    # Staged executables compile for — and arrays allocate on — the
    # resolved device (the index is part of the translation-cache
    # identity), which is what lets ThreadPoolBackend drive distinct
    # device groups genuinely in parallel. None = process default.
    device: int | None = None


@dataclasses.dataclass
class Prepared:
    """One staged measurement point: env + both pipeline stages.

    On the parametric path ``lowered``/``compiled`` are the ladder-shared
    :class:`ParamLowered`/:class:`ParamCompiled` (allocation happens at
    their capacity env) and ``env`` names this point's working set.
    """

    env: dict
    lowered: Lowered | ParamLowered
    compiled: Compiled | ParamCompiled

    @property
    def parametric(self) -> bool:
        return isinstance(self.lowered, ParamLowered)

    def executable(self) -> Callable:
        """A ``fn(tup) -> tup`` for this point (binds params if needed).

        Both paths thread donated buffers: the parametric bind closes
        over this point's param scalars, the specialized bind (donated
        measurement executables) threads each call's output tuple into
        the next so the timing loop never touches a consumed buffer."""
        if self.parametric:
            return self.compiled.bind(self.env)
        return self.compiled.bind()


class Driver:
    """Combine a PatternSpec with a driver template and measure it.

    ``pattern_factory(env)`` lets stream-count-style sweeps rebuild the
    pattern per point; for fixed patterns pass ``lambda env: pat``.

    Construction is staged (``lower -> compile -> execute``) through a
    :class:`~repro.core.staging.TranslationCache`; identical (pattern,
    schedule, template, backend, env) tuples never lower or compile
    twice across working-set loops, repeated runs, and sweeps. Pass
    ``cache=`` to isolate; the default pools work process-wide.
    """

    def __init__(self, pattern_factory: Callable[[Mapping[str, int]], PatternSpec],
                 config: DriverConfig,
                 cache: TranslationCache | None = None):
        if config.param_path not in ("auto", "strided", "gather"):
            raise ValueError(
                f"unknown param_path {config.param_path!r} "
                "(expected 'auto', 'strided', or 'gather')"
            )
        self.factory = pattern_factory
        self.cfg = config
        self.cache = cache if cache is not None else GLOBAL_CACHE

    # -- device pinning ------------------------------------------------------

    def _device(self):
        """The resolved jax device for ``cfg.device`` (None = default).
        Indices wrap modulo the device count so device-axis plans are
        portable to boxes with fewer devices."""
        if self.cfg.device is None:
            return None
        devs = jax.devices()
        return devs[self.cfg.device % len(devs)]

    def _dev_ctx(self):
        """Thread-local default-device scope wrapping every stage of
        this driver (lower, compile, allocate, execute). ``jax.default_
        device`` is a thread-local context, so concurrent backend
        workers pin their groups to distinct devices without fighting
        over process-global state. ``precompile``'s worker threads do
        NOT inherit the caller's context — compile thunks re-enter it
        themselves."""
        dev = self._device()
        return jax.default_device(dev) if dev is not None \
            else contextlib.nullcontext()

    # -- construction -------------------------------------------------------

    def _templated(
        self, env: Mapping[str, int]
    ) -> tuple[PatternSpec, Schedule, tuple[str, ...]]:
        """Apply the driver template: (pattern, schedule, grid_bands)."""
        cfg = self.cfg
        base = self.factory(env)
        sch = cfg.schedule or identity()
        if cfg.template == "independent":
            pat = independent_view(base, cfg.programs, cfg.pad)
            grid_bands = ("p",) + tuple(cfg.grid_bands or ())
        elif cfg.template == "unified":
            pat = base
            sch = unified_program_schedule(base, cfg.programs, env, sch)
            grid_bands = ("prog",) + tuple(cfg.grid_bands or ())
        else:
            raise ValueError(cfg.template)
        return pat, sch, grid_bands

    def lower(self, env: Mapping[str, int]) -> Lowered:
        """Stage 1: apply the driver template and resolve access plans.

        Note the ``independent`` template treats the caller's ``n`` as
        the *per-program* row extent (mirroring the paper's
        ``int N = n/t`` macro): callers pass per-program ``n`` and every
        space grows a leading ``programs`` axis of such rows.
        """
        cfg = self.cfg
        env = dict(env)
        pat, sch, grid_bands = self._templated(env)
        with self._dev_ctx():
            return stage_lower(
                pat, sch, env, cfg.backend,
                grid_bands=grid_bands if cfg.backend == "pallas" else None,
                device=cfg.device, cache=self.cache,
            )

    def lower_parametric(self, cap_env: Mapping[str, int],
                         params: tuple[str, ...] = ("n",),
                         param_path: str | None = None,
                         chunk: "int | tuple | None" = None,
                         assume_full: bool = False) -> ParamLowered:
        """Stage 1, shape-polymorphic: one artifact for a whole ladder,
        capacity-allocated at ``cap_env``.

        ``param_path``/``chunk``/``assume_full`` are the ladder-resolved
        regime — ``prepare``/``validate_parametric`` compute them from
        the concrete envs (including the per-env window-bounds check) so
        cache keys are deterministic per ladder. A direct call without
        ``param_path`` gets the **gather** regime: only ladder
        resolution can prove the strided windows safe for the rungs the
        caller intends to run, so the capacity-only entry point defaults
        to the regime that is safe at every admitted env. (The pallas
        backend has no gather regime, so a direct call without
        ``param_path='strided'`` raises ``SymbolicLowerError`` there.)
        """
        pat, sch, _ = self._templated(cap_env)
        with self._dev_ctx():
            return stage_lower_parametric(
                pat, sch, cap_env, params, self.cfg.backend,
                param_path=param_path or "gather", chunk=chunk,
                assume_full=assume_full, device=self.cfg.device,
                cache=self.cache
            )

    def _resolve_param_path(
        self, envs: Sequence[Mapping[str, int]],
        cap_env: Mapping[str, int],
    ) -> "tuple[str, int | tuple | None, bool]":
        """The concrete regime a viable ladder runs, as ``(path, chunk,
        assume_full)``: the config's preference checked against strided
        eligibility plus the exact per-env window-bounds test (a window
        that could leave the capacity shapes would be silently clamped —
        misaligned — so any such env demotes the whole ladder to
        gather). For strided ladders, ``param_strided_window`` resolves
        the window geometry: a lane-chunk int clamped to the smallest
        rung (1-D nests), or an N-D ``((band, C), ...)`` spec whose
        outer chunks are clamped to the smallest rung's extents (stencil
        nests) — either way buying the mask-free hot emitter wherever
        the ladder's smallest windows stay big."""
        cfg = self.cfg
        if cfg.param_path == "gather" and cfg.backend == "pallas":
            raise SymbolicLowerError(
                "the pallas parametric path has no gather regime; "
                "ineligible ladders specialize per size"
            )
        from .codegen import (
            param_strided_in_bounds,
            param_strided_plan,
            param_strided_window,
        )

        pat, sch, _ = self._templated(cap_env)
        pnest = sch.lower_symbolic(pat.domain, ("n",))
        if cfg.param_path == "gather":
            return self._gather_regime(pnest, cap_env)
        splan = param_strided_plan(pat, pnest)
        if splan is not None:
            chunk, full = param_strided_window(pnest, splan, list(envs),
                                               cap_env)
            if all(param_strided_in_bounds(pat, pnest, splan, e, cap_env,
                                           chunk)
                   for e in envs) and self._pallas_tiles_exactly(
                       pnest, splan, envs, cap_env, chunk, full):
                return "strided", chunk, full
        if cfg.param_path == "strided" or cfg.backend == "pallas":
            want = ("param_path='strided'" if cfg.param_path == "strided"
                    else "the pallas parametric path is strided-only")
            raise SymbolicLowerError(
                f"{want} but the ladder is not strided-eligible under "
                f"{cfg.template}/{(cfg.schedule or identity()).name}"
            )
        return self._gather_regime(pnest, cap_env)

    def _pallas_tiles_exactly(self, pnest, splan, envs, cap_env, chunk,
                              full: bool) -> bool:
        """Compiled pallas kernels start every lane window at a multiple
        of the lane chunk (see ``lower_pallas_parametric``), so such a
        ladder must be rank-1, mask-free, and tile each rung's lane
        extent exactly. Other backends and modes have no such contract."""
        from .codegen import pallas_platform_mode

        if self.cfg.backend != "pallas" or \
                pallas_platform_mode() != "compiled":
            return True
        if not full or not isinstance(chunk, int):
            return False
        ext = pnest.band_extents[splan.window_band]
        return all(ext.eval({**cap_env, **e}) % chunk == 0 for e in envs)

    @staticmethod
    def _gather_regime(pnest, cap_env: Mapping[str, int]):
        """The masked gather regime, when its index arrays fit: a ladder
        whose capacity exceeds the gather emitter's point cap has no
        shared executable and specializes per size instead."""
        from .codegen import _GATHER_POINT_CAP

        cap_pts = 1
        for e in pnest.band_extents:
            cap_pts *= max(0, e.eval(cap_env))
        if cap_pts > _GATHER_POINT_CAP:
            raise SymbolicLowerError(
                f"the gather regime would stage {cap_pts} capacity points "
                f"(cap {_GATHER_POINT_CAP}); specialize per size instead"
            )
        return "gather", None, False

    def _parametric_viable(self, envs: Sequence[Mapping[str, int]],
                           cap_env: Mapping[str, int]) -> bool:
        """Pre-flight (outside the cache, so failed probes never count as
        misses): the schedule must lower symbolically, every ladder point
        must satisfy the divisibility constraints, and the pattern
        factory must be structurally env-independent (one executable can
        only serve the ladder if every point shares its structure)."""
        cfg = self.cfg
        if cfg.backend not in ("jax", "pallas"):
            return False
        if cfg.donate is False:
            return False  # parametric executables are always donated
        # only the "n" param stays symbolic: points that disagree on any
        # *other* env entry cannot share one executable
        rest = {tuple(sorted((k, v) for k, v in e.items() if k != "n"))
                for e in envs}
        if len(rest) > 1:
            return False
        try:
            pat, sch, _ = self._templated(cap_env)
            if pat.kernel is not None:
                return False  # custom kernels bake env into the step
            pnest = sch.lower_symbolic(pat.domain, ("n",))
        except SymbolicLowerError:
            return False
        if not all(pnest.admits(e) for e in envs):
            return False
        try:
            # every point's arrays must fit the capacity allocation
            cap_shapes = {s.name: s.concrete_shape(cap_env)
                          for s in pat.spaces}
            for e in envs:
                for s in pat.spaces:
                    if any(g > c for g, c in zip(s.concrete_shape(e),
                                                 cap_shapes[s.name])):
                        return False
            cap_fp = fingerprint_pattern(pat)
            for e in envs:
                if fingerprint_pattern(self._templated(e)[0]) != cap_fp:
                    return False
        except (KeyError, ValueError, TypeError, ArithmeticError,
                SymbolicLowerError):
            # expected shape-probe outcomes (missing env symbol, invalid
            # extent arithmetic, unfingerprintable structure): the ladder
            # simply is not parametric. Anything else is a real fault and
            # propagates to the resilience layer instead of being
            # silently swallowed as "specialize".
            return False
        return True

    def _failure_context(self, env: Mapping[str, int] | None = None) -> dict:
        """Diagnosable payload for taxonomy wrappers: pattern, schedule,
        template, backend, env — enough to reproduce the fault from the
        record alone."""
        cfg = self.cfg
        ctx = {
            "template": cfg.template,
            "schedule": (cfg.schedule or identity()).name,
            "backend": cfg.backend,
            "programs": cfg.programs,
        }
        if env is not None:
            ctx["env"] = dict(env)
            try:
                ctx["pattern"] = self.factory(dict(env)).name
            except Exception:
                pass  # the factory itself may be the fault
        return ctx

    def _preflight(self, pat: PatternSpec, alloc_env: Mapping[str, int]) -> None:
        """Working-set pre-flight: refuse allocations that would blow the
        capacity budget — a structured ``CapacityRefused`` instead of an
        OOM kill. ``alloc_env`` is the env the arrays are materialized
        at (the ladder capacity on the parametric path, the point's own
        env specialized — which is why demoting parametric→specialized
        can rescue the smaller rungs of a refused ladder)."""
        budget = (self.cfg.capacity_budget_bytes
                  if self.cfg.capacity_budget_bytes is not None
                  else default_capacity_budget())
        if budget is None:
            return
        ws = sum(
            int(np.prod(s.concrete_shape(alloc_env)))
            * np.dtype(s.dtype).itemsize
            for s in pat.spaces
        )
        need = 2 * ws  # seed tuple + output buffers live simultaneously
        if need > budget:
            raise CapacityRefused(
                f"refusing allocation: working set {ws} bytes (x2 for "
                f"in/out buffers = {need}) exceeds the capacity budget "
                f"of {budget} bytes at n={alloc_env.get('n')}",
                context={**self._failure_context(alloc_env),
                         "pattern": pat.name,
                         "working_set_bytes": int(ws),
                         "required_bytes": int(need),
                         "budget_bytes": int(budget)})

    def build(self, env: Mapping[str, int]):
        """Stage 1+2 plus initial arrays.

        Returns ``(pattern, schedule, env, compiled, arrays0, names)``;
        ``compiled(tup)`` executes ``ntimes`` sweeps under the configured
        barrier regime on a tuple of arrays ordered by ``names``.
        """
        cfg = self.cfg
        lowered = self.lower(env)
        with self._dev_ctx():
            compiled = lowered.compile(
                ntimes=cfg.ntimes, sync_every_rep=cfg.sync_every_rep,
                cache=self.cache,
            )
            pat = lowered.pattern
            arrays0 = {k: jnp.asarray(v)
                       for k, v in pat.allocate(lowered.env).items()}
        names = compiled.names
        return (pat, lowered.schedule, lowered.env, compiled,
                tuple(arrays0[k] for k in names), names)

    @staticmethod
    def _point_envs(points: "Sequence[int | Mapping[str, int]]",
                    env_extra: Mapping[str, int] | None) -> list[dict]:
        """Normalize measurement points to env dicts: a bare int is the
        working set ``n`` (the ladder form); a mapping is a full env
        point (the plan-engine form, any env axes)."""
        envs = []
        for p in points:
            if isinstance(p, Mapping):
                e = {str(k): int(v) for k, v in p.items()}
            else:
                e = {"n": int(p)}
            e.update({str(k): int(v) for k, v in (env_extra or {}).items()})
            envs.append(e)
        return envs

    def prepare(self, working_sets: "Sequence[int | Mapping[str, int]]",
                env_extra: Mapping[str, int] | None = None,
                parallel: bool = True) -> list[Prepared]:
        """Stage all measurement points (ints = working sets, mappings =
        full env points).

        Parametric path (``cfg.parametric``): the whole ladder maps onto
        ONE ``ParamLowered``/``ParamCompiled`` pair keyed at the ladder's
        capacity (max n) — the first point pays the single lower+compile,
        the rest are cache hits, and ``run`` passes each point's ``n`` at
        call time. Specialized path: lower serially (cheap, GIL-bound),
        then AOT-compile the points concurrently (XLA releases the GIL).

        Runs inside a ``repro.prepare`` span (``path`` = parametric or
        specialized, ``points``), its regime resolution inside
        ``repro.resolve``.
        """
        envs = self._point_envs(working_sets, env_extra)
        with spans.span("repro.prepare", points=len(envs)) as sp:
            preps = self._prepare(envs, working_sets, parallel)
            sp.attrs["path"] = ("parametric" if preps and preps[0].parametric
                                else "specialized")
        return preps

    def _prepare(self, envs: list[dict], working_sets,
                 parallel: bool) -> list[Prepared]:
        cfg = self.cfg
        # "auto" only shares when there is a ladder to share across: a
        # single-point run gains nothing from the parametric regime and
        # would pay its chunked-gather overhead for free, so it keeps the
        # specialized fast path. parametric=True still forces sharing.
        want_parametric = cfg.parametric and not (
            cfg.parametric == "auto" and len({e["n"] for e in envs}) < 2
        )
        if want_parametric:
            cap_env = max(envs, key=lambda e: e["n"])
            resolved = None
            with spans.span("repro.resolve"):
                if self._parametric_viable(envs, cap_env):
                    try:
                        # single resolution pass: a forced-strided ladder
                        # that is not window-safe raises here and falls
                        # through to specialization (or re-raises under
                        # parametric=True)
                        resolved = self._resolve_param_path(envs, cap_env)
                    except SymbolicLowerError:
                        resolved = None
            if resolved is not None:
                path, chunk, full = resolved
                preps = []
                for env in envs:
                    try:
                        lw = self.lower_parametric(
                            cap_env, param_path=path, chunk=chunk,
                            assume_full=full)
                    except (BenchFailure, SymbolicLowerError):
                        raise
                    except Exception as e:
                        raise LowerFailure(
                            f"{type(e).__name__}: {e}",
                            context=self._failure_context(cap_env),
                            cause=e) from e
                    try:
                        with self._dev_ctx():
                            c = lw.compile(
                                ntimes=cfg.ntimes,
                                sync_every_rep=cfg.sync_every_rep,
                                cache=self.cache,
                            )
                    except BenchFailure:
                        raise
                    except Exception as e:
                        raise CompileFailure(
                            f"{type(e).__name__}: {e}",
                            context=self._failure_context(cap_env),
                            cause=e) from e
                    preps.append(Prepared(env=env, lowered=lw, compiled=c))
                return preps
            if cfg.parametric is True:
                raise SymbolicLowerError(
                    f"parametric=True but the ladder {list(working_sets)} "
                    f"cannot share one executable under {cfg.template}/"
                    f"{(cfg.schedule or identity()).name}"
                )
        lowereds = []
        for env in envs:
            try:
                lowereds.append((env, self.lower(env)))
            except (BenchFailure, SymbolicLowerError):
                raise
            except Exception as e:
                raise LowerFailure(
                    f"{type(e).__name__}: {e}",
                    context=self._failure_context(env), cause=e) from e
        # measurement executables donate their buffers (no per-call
        # working-set-sized copy — the same copy-free economics as the
        # parametric path, so strided-vs-specialized comparisons are
        # fair on both sides); Prepared.executable() threads the
        # consumed tuples. This holds for pallas too: input_output_
        # aliases covers the kernel-internal aliasing, donation closes
        # the remaining jit-boundary copy. donate=False (the last
        # demotion rung) forces per-call copies everywhere.
        donate = (cfg.backend in ("jax", "pallas")) if cfg.donate is None \
            else bool(cfg.donate)

        def _compile_thunk(lw, env):
            def thunk():
                try:
                    # re-enter the device scope: precompile runs thunks
                    # in worker threads, which do not inherit the
                    # caller's thread-local default device
                    with self._dev_ctx():
                        return lw.compile(
                            ntimes=cfg.ntimes,
                            sync_every_rep=cfg.sync_every_rep,
                            donate=donate, cache=self.cache,
                        )
                except BenchFailure:
                    raise
                except Exception as e:
                    raise CompileFailure(
                        f"{type(e).__name__}: {e}",
                        context=self._failure_context(env), cause=e) from e
            return thunk

        thunks = [_compile_thunk(lw, env) for env, lw in lowereds]
        compiled = (precompile(thunks) if parallel
                    else [t() for t in thunks])
        return [
            Prepared(env=env, lowered=lw, compiled=c)
            for (env, lw), c in zip(lowereds, compiled)
        ]

    # -- validation (the <kernel>_val.in stage) ------------------------------

    @spans.spanned("repro.validate")
    def validate(self, env: Mapping[str, int] | None = None) -> None:
        """Replay the run schedule against the numpy oracle.

        Memoized per lowered key: a sweep validates each variant once,
        not once per working set / per repeated call.
        """
        cfg = self.cfg
        n = cfg.validate_n or 64
        env = dict(env or {"n": n})
        lowered = self.lower(env)
        vkey = ("validate", lowered.key) if lowered.key is not None else None
        if vkey is not None and self.cache.was_validated(vkey):
            return
        pat, sch, env2 = lowered.pattern, lowered.schedule, lowered.env
        arrays = pat.allocate(env2)
        want = serial_oracle(pat, lowered.nest, arrays, env2, ntimes=2)
        with self._dev_ctx():
            got = {k: jnp.asarray(v) for k, v in arrays.items()}
            for _ in range(2):
                got = lowered.step(got)
        for k in want:
            np.testing.assert_allclose(
                np.asarray(got[k]), want[k], rtol=1e-5, atol=1e-5,
                err_msg=f"space {k} diverged under {sch.name}/{cfg.template}",
            )
        if vkey is not None:
            self.cache.mark_validated(vkey)

    # -- measurement ---------------------------------------------------------

    @spans.spanned("repro.measure")
    def measure_point(self, p: Prepared) -> Record:
        """Measure ONE staged point — the per-point isolation unit the
        plan engine wraps (a fault here fails this point, not the
        group). Runs the working-set pre-flight, times under the
        configured quality policy, and stamps ``extra.timing_quality``
        on the record."""
        cfg = self.cfg
        pat, env = p.lowered.pattern, p.env
        # Parametric points allocate at the shared capacity env (the
        # executable's static shapes); the kernel only touches the
        # [0, n) region, and all *accounting* below uses the actual
        # per-point env so records match the specialized path.
        self._preflight(pat, p.lowered.env)
        dev = self._device()
        try:
            with self._dev_ctx():
                arrays0 = {
                    k: jnp.asarray(v)
                    for k, v in pat.allocate(p.lowered.env).items()
                }
                tup = tuple(arrays0[k] for k in p.compiled.names)
                timing = time_fn(
                    p.executable(), tup, reps=cfg.reps, warmup=1,
                    compile_seconds=p.compiled.compile_seconds,
                    target_cv=cfg.target_cv, max_reps=cfg.max_reps,
                    budget_s=cfg.time_budget_s,
                )
        except BudgetExceeded as e:
            for k, v in self._failure_context(env).items():
                e.context.setdefault(k, v)
            raise
        pts = pat.domain.point_count(env)
        bpp = pat.bytes_per_point()
        total_bytes = bpp * pts * cfg.ntimes
        mix_extra: dict = {}
        if pat.mix is not None:
            # multi-pattern mixes: the statement accounts the primary
            # component only; total traffic is every component's bytes,
            # and the per-component split rides into extra["mix"]
            comps = [dict(c) for c in pat.mix["components"]]
            total_bytes = sum(c["bytes"] for c in comps) * cfg.ntimes
            mix_extra = {"mix": {"primary": pat.mix["primary"],
                                 "components": comps}}
        ws_bytes = sum(
            int(np.prod(s.concrete_shape(env)))
            * np.dtype(s.dtype).itemsize
            for s in pat.spaces
        )
        rec = Record(
            pattern=pat.name,
            template=cfg.template,
            schedule=p.lowered.schedule.name,
            backend=cfg.backend,
            n=int(env["n"]),
            working_set_bytes=ws_bytes,
            programs=cfg.programs,
            ntimes=cfg.ntimes,
            seconds=timing.seconds,
            gbs=total_bytes / timing.seconds / 1e9,
            gflops=pat.flops_per_point * pts * cfg.ntimes
            / timing.seconds / 1e9,
            level=classify_level(ws_bytes),
            extra={
                "barrier": cfg.sync_every_rep,
                "points": int(pts),
                "compile_seconds": p.compiled.compile_seconds,
                "lower_seconds": p.lowered.lower_seconds,
                "cache_hit": p.compiled.from_cache,
                "parametric": p.parametric,
                "param_path": (p.compiled.param_path if p.parametric
                               else "specialized"),
                "donated": bool(getattr(p.compiled, "donated", True)),
                "timing_quality": timing.quality(),
                **({"device": {"axis": int(cfg.device),
                               "id": int(dev.id),
                               "platform": str(dev.platform)}}
                   if dev is not None else {}),
                **({"pallas_mode": p.lowered.pallas_mode}
                   if cfg.backend == "pallas" else {}),
                **({"derived": dict(pat.derived)}
                   if pat.derived is not None else {}),
                **({"trace": dict(pat.trace)}
                   if pat.trace is not None else {}),
                **mix_extra,
                **({"capacity": int(p.lowered.cap_env["n"]),
                    "param_window_rank": int(
                        p.compiled.param_window_rank)}
                   if p.parametric else {}),
            },
        )
        if cfg.measured:
            rec.extra.update(hlo_counters(p.compiled))
            rec.extra.update(self._traffic(pat, env).as_dict())
        return rec

    def run(self, working_sets: "Sequence[int | Mapping[str, int]]",
            env_extra: Mapping[str, int] | None = None) -> list[Record]:
        return [self.measure_point(p)
                for p in self.prepare(working_sets, env_extra)]

    @spans.spanned("repro.validate")
    def validate_parametric(self,
                            working_sets: "Sequence[int | Mapping[str, int]]",
                            env_extra: Mapping[str, int] | None = None,
                            max_check_n: int | None = None) -> None:
        """Check the ladder-shared executable point-by-point against the
        specialized serial oracle: for a working set, the [0, n)
        region of the parametric result must match the oracle run at
        exactly that n (the paper's ``<kernel>_val.in`` stage, replayed
        for the shape-polymorphic path).

        The executable is built at the ladder's true capacity, but
        ``max_check_n`` bounds which points are oracle-replayed (the
        serial oracle's point-loop fallback is O(points) Python); the
        smallest point is always checked. Memoized per (ladder key,
        checked points) like :meth:`validate`.
        """
        cfg = self.cfg
        envs = self._point_envs(working_sets, env_extra)
        cap_env = max(envs, key=lambda e: e["n"])
        if not self._parametric_viable(envs, cap_env):
            raise SymbolicLowerError(
                f"ladder {list(working_sets)} is not parametric under "
                f"{cfg.template}"
            )
        path, chunk, full = self._resolve_param_path(envs, cap_env)
        if max_check_n is not None:
            lo = min(envs, key=lambda e: e["n"])
            envs = [e for e in envs if e["n"] <= max_check_n] or [lo]
        lw = self.lower_parametric(cap_env, param_path=path, chunk=chunk,
                                   assume_full=full)
        vkey = None
        if lw.key is not None:
            vkey = ("pvalidate", lw.key,
                    tuple(sorted(e["n"] for e in envs)))
            if self.cache.was_validated(vkey):
                return
        pat = lw.pattern
        cap_arrays = pat.allocate(cap_env)
        for env in envs:
            pvals = tuple(np.int32(env[p]) for p in lw.params)
            with self._dev_ctx():
                got = {k: jnp.asarray(v) for k, v in cap_arrays.items()}
                for _ in range(2):
                    got = lw.step(got, pvals)
            spec = self.lower(env)
            want = serial_oracle(
                spec.pattern, spec.nest, spec.pattern.allocate(env), env,
                ntimes=2,
            )
            for k in want:
                region = tuple(
                    slice(0, d) for d in pat.space(k).concrete_shape(env)
                )
                np.testing.assert_allclose(
                    np.asarray(got[k])[region], want[k],
                    rtol=1e-5, atol=1e-5,
                    err_msg=(
                        f"space {k} diverged on the parametric path at "
                        f"n={env['n']} (capacity {cap_env['n']})"
                    ),
                )
        if vkey is not None:
            self.cache.mark_validated(vkey)

    def _traffic(self, pat: PatternSpec, env: Mapping[str, int]):
        """Analytic tile traffic for the current template split (1D)."""
        cfg = self.cfg
        written = pat.statement.write.space
        slices: list[dict[str, tuple[int, int]]] = []
        if cfg.template == "independent":
            # rows are (n + pad) apart in the flat layout
            row = Affine.of(pat.space(written).shape[1]).eval(env)
            per = pat.domain.dims[1].extent(env)
            lo0 = pat.domain.dims[1].lo.eval(env)
            for p in range(cfg.programs):
                flat0 = p * row + lo0
                slices.append(
                    {s.name: (flat0, flat0 + per) for s in pat.spaces}
                )
        else:
            d0 = pat.domain.dims[0]
            lo, ext = d0.lo.eval(env), d0.extent(env)
            chunk = ext // cfg.programs
            for p in range(cfg.programs):
                a = lo + p * chunk
                slices.append({s.name: (a, a + chunk) for s in pat.spaces})
        return tile_traffic(
            spaces={s.name: s.concrete_shape(env) for s in pat.spaces},
            program_slices=slices,
            written=written,
            itemsize=np.dtype(pat.space(written).dtype).itemsize,
        )
