"""Code generation: lower (PatternSpec, Schedule) to executable JAX.

This is the analogue of ISCC's ``codegen`` call, retargeted at two
backends and split into explicit stages so drivers, sweeps, and the
autotuner can share work through the translation cache (see
``staging.py``):

``plan_nest``
    Stage 0 of the pipeline: lower the schedule against a concrete env
    and resolve every access into per-band ``(coeffs, const)`` rows.
    Plan building is pure Python (no tracing) and is what the staged
    ``Lowered`` artifact memoizes.

``lower_jax``
    Vectorized jax.numpy. Instances whose affine maps use **one band per
    domain dim** (identity, interchange, reverse, interleave, unroll — all
    of the paper's triad-family experiments) lower to static strided-slice
    reads + ``.at[...].set`` writes, which XLA fuses into a single
    streaming loop — the moral equivalent of the paper's generated C.
    General maps (tiling, skew) lower to a gather/scatter form whose
    indices are built *inside* the traced program from
    ``lax.broadcasted_iota`` (never embedded as host constants), so large
    grids stay cheap to trace and compile.

``lower_jax_parametric``
    Shape-polymorphic twin of ``lower_jax``: the working-set parameters
    become traced operands so one AOT executable serves a whole ladder.
    Two regimes, selected by ``param_path``: the **strided fast path**
    (``lax.dynamic_slice``/``dynamic_update_slice`` windows, chosen
    whenever the symbolic nest satisfies the same single-band precondition
    as the specialized strided path — per-call cost matches it; windows
    are **multi-dimensional** for stencil nests, covering an
    (i-chunk x j-chunk x ...) box per step over every dynamic band the
    write references, with stencil reads fused into one halo'd hull
    slice per space) and the **masked gather/scatter** fallback for
    everything else (guards, splits, diagonals). ``step.param_path`` /
    ``step.param_window_rank`` report what was built.

``lower_pallas``
    A Pallas kernel per schedule. Loop bands become the ``grid``; vector
    bands become the block. Refs are *unblocked* (whole array) and the
    kernel issues explicit dynamic slices — on TPU this corresponds to the
    HBM->VMEM manual-DMA style used for halo'd stencils. Blocked-
    ``BlockSpec`` showcase kernels live in ``repro.kernels``. Execution
    mode is resolved once per process (``pallas_platform_mode``): the
    interpreter on the CPU backend only, compiled everywhere else. Whole
    operands must fit the VMEM budget (``PALLAS_VMEM_BUDGET``).

``lower_pallas_parametric``
    Shape-polymorphic twin of ``lower_pallas``, strided regime only: the
    ``param_strided_window`` specs become pallas *grid* steps over N-D
    ``pl.ds`` windows, with the working-set parameters read from a traced
    i32 operand in SMEM — one pallas executable serves a whole working-set
    ladder, same contract as ``lower_jax_parametric``'s strided path
    (compiled, the lane windows also start aligned: see its docstring).

``serial_oracle``
    Pure-numpy execution in generated-code order. The ground truth every
    backend is validated against (the paper's ``<kernel>_val.in`` stage).
    Nests whose statement never reads its written space and whose maps
    admit the strided-slice form are executed with vectorized numpy
    slices (provably order-independent there); everything else falls
    back to the point-by-point loop.

Traversal-direction note: slices generated from the same band are paired
elementwise across reads and the write, so negative-coefficient maps
(reverse) need no flips — pairing by band value is automatically
consistent *provided all accesses agree on coefficient sign per band*,
which holds for every Schedule-generated nest (transforms rewrite all
instances uniformly). Hand-built accesses that mix signs fall back to the
gather path (checked).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .domain import Affine
from .errors import LowerFailure
from .pattern import Access, PatternSpec, mix_space
from .schedule import (
    LoweredInstance,
    LoweredNest,
    ParamInstance,
    ParamNest,
    Schedule,
    _const_int,
)

__all__ = [
    "serial_oracle",
    "replay_component",
    "lower_jax",
    "lower_mix",
    "lower_jax_parametric",
    "lower_pallas",
    "lower_pallas_parametric",
    "pallas_platform_mode",
    "resolve_access",
    "resolve_access_symbolic",
    "plan_nest",
    "NestPlan",
    "ParamStridedPlan",
    "param_strided_plan",
    "param_strided_in_bounds",
    "param_strided_window",
    "param_window_bands",
    "windowed_oracle",
]

# Indices are now built in-program from broadcasted_iota (no host-side
# constants), so the cap only bounds runtime index-array memory.
_GATHER_POINT_CAP = 1 << 26

# Named scopes of the XLA emitters' read, combine and write: stable names
# in each compiled op's ``op_name`` metadata, whatever names XLA gives the
# instructions (metadata only: the ops are the same with or without them).
SCOPE_READ = "repro.window.read"
SCOPE_COMBINE = "repro.window.combine"
SCOPE_WRITE = "repro.window.write"

# Lane-block size of the parametric (shape-polymorphic) path: points are
# executed in fixed-shape chunks under a dynamic trip count, so the work
# a call performs scales with the runtime working set, not the capacity.
_PARAM_CHUNK = 8192


# ---------------------------------------------------------------------------
# Access resolution: Access (affine in iterator names) -> per-dim (row, const)
# over *bands*, by composing with a LoweredInstance.
# ---------------------------------------------------------------------------


def resolve_access(
    acc: Access, nest: LoweredNest, inst: LoweredInstance,
    iter_names: tuple[str, ...], env: Mapping[str, int],
) -> list[tuple[tuple[int, ...], int]]:
    """Compose an access's affine index with an instance's band map.

    Returns, per array dim, ``(coeff_per_band, const)`` such that
    ``array_index = coeff . bands + const``.
    """
    out = []
    pos = {n: i for i, n in enumerate(iter_names)}
    for ix in acc.resolved():
        ix = Affine.of(ix.subs(env))  # fold parameters like n
        row = [0] * nest.n_bands
        const = ix.const
        for sym, c in ix.coeffs:
            if sym not in pos:
                raise KeyError(f"access symbol {sym!r} is not an iterator or param")
            d = pos[sym]
            const += c * inst.c[d]
            for b in range(nest.n_bands):
                row[b] += c * inst.A[d][b]
        out.append((tuple(row), const))
    return out


def _signs_consistent(plans) -> bool:
    """All accesses in each instance agree on coeff sign per band."""
    for racc, wacc in plans:
        sign: dict[int, int] = {}
        for rows in list(racc) + [wacc]:
            for row, _ in rows:
                for b, c in enumerate(row):
                    if c == 0:
                        continue
                    s = 1 if c > 0 else -1
                    if sign.setdefault(b, s) != s:
                        return False
    return True


# ---------------------------------------------------------------------------
# Access plans (stage 0 of the pipeline)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class NestPlan:
    """Resolved access plans for one (pattern, schedule, env) instance.

    ``plans[k] = (read_rows, write_rows)`` for statement instance k, where
    each rows entry is ``resolve_access`` output: per array dim,
    ``(coeff_per_band, const)``. Building a plan never traces; it is the
    unit of work the translation cache's lower stage memoizes.
    """

    nest: LoweredNest
    plans: tuple
    guarded: bool
    single_band: bool
    signs_ok: bool

    @property
    def fast(self) -> bool:
        """Strided-slice fast path precondition."""
        return not self.guarded and self.single_band and self.signs_ok


def plan_nest(pattern: PatternSpec, schedule: Schedule,
              env: Mapping[str, int], nest: LoweredNest | None = None,
              ) -> NestPlan:
    """Lower the schedule and resolve every access against its bands."""
    if nest is None:
        nest = schedule.lower(pattern.domain, env)
    return _plan_from_nest(pattern, nest, env)


def _plan_from_nest(pattern: PatternSpec, nest: LoweredNest,
                    env: Mapping[str, int]) -> NestPlan:
    stmt = pattern.statement
    iter_names = pattern.domain.names
    plans = tuple(
        (
            tuple(
                resolve_access(a, nest, inst, iter_names, env)
                for a in stmt.reads
            ),
            resolve_access(stmt.write, nest, inst, iter_names, env),
        )
        for inst in nest.instances
    )
    return NestPlan(
        nest=nest,
        plans=plans,
        guarded=nest.needs_guard(),
        single_band=all(_single_band_per_dim(nest, i) for i in nest.instances),
        signs_ok=_signs_consistent(plans),
    )


# ---------------------------------------------------------------------------
# Serial oracle
# ---------------------------------------------------------------------------


def serial_oracle(
    pattern: PatternSpec, nest: LoweredNest, arrays: dict[str, np.ndarray],
    env: Mapping[str, int], ntimes: int = 1, *, force_loop: bool = False,
) -> dict[str, np.ndarray]:
    """Execute the scheduled nest in numpy. Copies inputs.

    Fast path: when the statement never reads its written space, the nest
    needs no guards, and every instance admits the strided-slice form,
    sweeps are executed with vectorized numpy slice assignments — result
    is provably identical to the point loop (reads cannot observe writes
    within a sweep; schedule bijectivity keeps instance writes disjoint).
    ``force_loop=True`` pins the point-by-point reference (tests).
    """
    if pattern.oracle is not None:
        # serial-dependent patterns (pointer chase) carry their own
        # ground truth; the affine replay below cannot express them
        return pattern.oracle(pattern, arrays, env, ntimes)
    arrays = {k: np.array(v) for k, v in arrays.items()}
    names = pattern.domain.names
    stmt = pattern.statement
    if not force_loop:
        plan = _oracle_plan(pattern, nest, env)
        if plan is not None:
            return _oracle_vectorized(pattern, plan, arrays, env, ntimes)
    for _ in range(ntimes):
        for point in nest.executed_points():
            scope = dict(zip(names, point))
            scope.update(env)
            vals = []
            for acc in stmt.reads:
                idx = tuple(Affine.of(ix).eval(scope) for ix in acc.index)
                vals.append(np.asarray(arrays[acc.space][idx]))
            res = stmt.combine(vals, dict(env))
            widx = tuple(Affine.of(ix).eval(scope) for ix in stmt.write.index)
            arrays[stmt.write.space][widx] = res
    return arrays


def replay_component(comp: PatternSpec, arrays: dict[str, np.ndarray],
                     env: Mapping[str, int], ntimes: int = 1) -> dict:
    """Numpy ground truth for ONE mix component: its own oracle when it
    carries one (value-dependent components), else the serial oracle
    over its identity nest. Mix components execute under the identity
    schedule inside the fused step, so the identity nest is exactly what
    :func:`lower_mix` runs."""
    from .schedule import identity

    if comp.oracle is not None:
        return comp.oracle(comp, arrays, env, ntimes)
    nest = identity().lower(comp.domain, env)
    return serial_oracle(comp, nest, arrays, env, ntimes=ntimes)


def lower_mix(pattern: PatternSpec, components: tuple) -> Callable:
    """Build the fused step of a :func:`~repro.core.pattern.mix_patterns`
    spec: every component's own step (affine statements lower through
    :func:`lower_jax`; custom-kernel components contribute their kernel)
    runs once per sweep against its ``m{k}_``-namespaced slice of the
    array dict, inside ONE jitted executable — the access streams share
    the compiled program, so the fused ``ntimes`` repetition loop
    alternates the components' sweeps through the memory system.

    ``components`` is the concretized ``(label, spec, env)`` tuple the
    mix kernel closed over (each component's env is baked — mixes always
    specialize, like every custom-kernel pattern).
    """
    from .schedule import identity

    steps = tuple(
        (k, comp, lower_jax(comp, identity(), cenv))
        for k, (_label, comp, cenv) in enumerate(components)
    )

    def step(arrays):
        arrays = dict(arrays)
        for k, comp, st in steps:
            sub = {s.name: arrays[mix_space(k, s.name)] for s in comp.spaces}
            sub = st(sub)
            for s in comp.spaces:
                arrays[mix_space(k, s.name)] = sub[s.name]
        return arrays

    return step


def _oracle_plan(pattern: PatternSpec, nest: LoweredNest,
                 env: Mapping[str, int]) -> NestPlan | None:
    """NestPlan if the vectorized oracle path is provably safe, else None."""
    stmt = pattern.statement
    if any(a.space == stmt.write.space for a in stmt.reads):
        return None
    try:
        plan = _plan_from_nest(pattern, nest, env)
    except Exception:
        return None
    return plan if plan.fast else None


def _oracle_vectorized(pattern: PatternSpec, plan: NestPlan,
                       arrays: dict[str, np.ndarray],
                       env: Mapping[str, int], ntimes: int,
                       ) -> dict[str, np.ndarray]:
    """Numpy mirror of the strided-slice fast path (see lower_jax)."""
    stmt = pattern.statement
    nest = plan.nest
    for _ in range(ntimes):
        for racc, wacc in plan.plans:
            w_sl, w_bands = [], []
            for row, const in wacc:
                sl, b = _slice_for(row, const, nest.band_extents)
                w_sl.append(sl)
                w_bands.append(b)
            vals = []
            for acc, rows in zip(stmt.reads, racc):
                sls, bands_order = [], []
                for row, const in rows:
                    sl, b = _slice_for(row, const, nest.band_extents)
                    sls.append(sl)
                    bands_order.append(b)
                v = arrays[acc.space][tuple(sls)]
                perm = _axis_perm(bands_order, w_bands)
                if perm is not None:
                    v = np.transpose(v, perm)
                vals.append(v)
            res = stmt.combine(vals, dict(env))
            tgt = arrays[stmt.write.space]
            tgt[tuple(w_sl)] = np.asarray(res).astype(tgt.dtype)
    return arrays


# ---------------------------------------------------------------------------
# Vectorized JAX backend
# ---------------------------------------------------------------------------


def _single_band_per_dim(nest: LoweredNest, inst: LoweredInstance) -> bool:
    """True if each domain dim reads exactly one band and each band feeds
    at most one dim — the strided-slice fast path precondition."""
    used: dict[int, int] = {}
    for d in range(nest.rank):
        nz = [b for b, c in enumerate(inst.A[d]) if c != 0]
        if len(nz) != 1:
            return False
        b = nz[0]
        if b in used:
            return False
        used[b] = d
    return True


def _slice_for(row: tuple[int, ...], const: int,
               extents: tuple[int, ...]) -> tuple[slice, int]:
    """Static strided slice covering ``{row.b + const : b in band box}``.

    ``row`` must have at most one nonzero coeff. The slice is always
    ascending-index; see the traversal-direction note in the module doc.
    Returns (slice, band_index) with band_index=-1 for constant indices.
    """
    nz = [(b, c) for b, c in enumerate(row) if c != 0]
    if not nz:
        return slice(const, const + 1), -1
    (b, c), = nz
    e = extents[b]
    if c > 0:
        return slice(const, const + c * (e - 1) + 1, c), b
    lo = const + c * (e - 1)
    return slice(lo, const + 1, -c), b


def _axis_perm(src_bands: list[int], dst_bands: list[int]):
    """Permutation taking value axes (ordered by src_bands) to dst order,
    or None if already aligned / not a permutation (broadcast case)."""
    if src_bands == dst_bands:
        return None
    if sorted(src_bands) != sorted(dst_bands):
        return None
    return tuple(src_bands.index(b) for b in dst_bands)


def lower_jax(
    pattern: PatternSpec, schedule: Schedule, env: Mapping[str, int],
    *, force_gather: bool = False, plan: NestPlan | None = None,
) -> Callable[[dict[str, jnp.ndarray]], dict[str, jnp.ndarray]]:
    """Build ``step(arrays) -> arrays`` executing one sweep of the pattern.

    ``plan`` lets the staged pipeline reuse an already-resolved NestPlan
    instead of re-deriving access rows.
    """
    if pattern.kernel is not None:
        # serial-dependent patterns replace the generated step wholesale;
        # schedule transforms would be silently ignored, so refuse them
        if schedule.transforms:
            raise ValueError(
                f"pattern {pattern.name!r} has a custom kernel; schedule "
                f"{schedule.name!r} cannot be applied to it"
            )
        return pattern.kernel(pattern, env)
    if plan is None:
        plan = plan_nest(pattern, schedule, env)
    nest = plan.nest
    stmt = pattern.statement
    plans = plan.plans

    if plan.fast and not force_gather:
        def step(arrays: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
            arrays = dict(arrays)
            for racc, wacc in plans:
                w_sl, w_bands = [], []
                for row, const in wacc:
                    sl, b = _slice_for(row, const, nest.band_extents)
                    w_sl.append(sl)
                    w_bands.append(b)
                vals = []
                with jax.named_scope(SCOPE_READ):
                    for acc, rr in zip(stmt.reads, racc):
                        sls, bands_order = [], []
                        for row, const in rr:
                            sl, b = _slice_for(row, const,
                                               nest.band_extents)
                            sls.append(sl)
                            bands_order.append(b)
                        v = arrays[acc.space][tuple(sls)]
                        perm = _axis_perm(bands_order, w_bands)
                        if perm is not None:
                            v = jnp.transpose(v, perm)
                        vals.append(v)
                with jax.named_scope(SCOPE_COMBINE):
                    res = stmt.combine(vals, dict(env))
                tgt = arrays[stmt.write.space]
                with jax.named_scope(SCOPE_WRITE):
                    arrays[stmt.write.space] = tgt.at[tuple(w_sl)].set(
                        jnp.asarray(res).astype(tgt.dtype)
                    )
            return arrays

        return step

    # -- gather/scatter general path ---------------------------------------
    # Band coordinates come from lax.broadcasted_iota inside the traced
    # program, so no index constants are embedded in the HLO and trace
    # size stays O(accesses), not O(points).
    n_pts = int(np.prod(nest.band_extents)) if nest.band_extents else 1
    if n_pts > _GATHER_POINT_CAP:
        raise ValueError(
            f"gather path would materialize {n_pts} index points; "
            "use lower_pallas"
        )
    guarded = plan.guarded
    used_bands = sorted({
        b
        for racc, wacc in plans
        for rows in list(racc) + [wacc]
        for row, _ in rows
        for b, c in enumerate(row)
        if c != 0
    } | ({
        b
        for inst in nest.instances
        for d in range(nest.rank)
        for b, c in enumerate(inst.A[d])
        if c != 0
    } if guarded else set()))
    extents = nest.band_extents

    def step(arrays: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        arrays = dict(arrays)
        cols = {
            b: jax.lax.broadcasted_iota(jnp.int32, extents, b).reshape(-1)
            for b in used_bands
        }

        def lin(row, const):
            acc = None
            for b, c in enumerate(row):
                if c == 0:
                    continue
                term = c * cols[b]
                acc = term if acc is None else acc + term
            if acc is None:
                return jnp.full((n_pts,), const, jnp.int32)
            return acc + jnp.int32(const)

        for (racc, wacc), inst in zip(plans, nest.instances):
            mask = None
            if guarded:
                mask = jnp.ones((n_pts,), bool)
                for d in range(nest.rank):
                    it = lin(inst.A[d], inst.c[d])
                    mask &= (it >= nest.domain_lo[d]) & (it < nest.domain_hi[d])
            # OOB reads clamp (jit default); their lanes are dropped on write
            with jax.named_scope(SCOPE_READ):
                vals = [
                    arrays[acc.space][tuple(lin(row, const)
                                            for row, const in rows)]
                    for acc, rows in zip(stmt.reads, racc)
                ]
            with jax.named_scope(SCOPE_COMBINE):
                res = stmt.combine(vals, dict(env))
            tgt = arrays[stmt.write.space]
            with jax.named_scope(SCOPE_WRITE):
                widx = tuple(lin(row, const) for row, const in wacc)
                if mask is not None:
                    widx = tuple(jnp.where(mask, ix, -1) for ix in widx)
                arrays[stmt.write.space] = tgt.at[widx].set(
                    jnp.asarray(res).astype(tgt.dtype), mode="drop"
                )
        return arrays

    return step


# ---------------------------------------------------------------------------
# Parametric (shape-polymorphic) JAX backend
# ---------------------------------------------------------------------------


def resolve_access_symbolic(
    acc: Access, pnest: ParamNest, inst: ParamInstance,
    iter_names: tuple[str, ...],
) -> list[tuple[tuple[Affine, ...], Affine]]:
    """Symbolic twin of :func:`resolve_access`: compose an access with a
    :class:`ParamInstance` without resolving parameters, so per array dim
    ``array_index = row . bands + const`` with Affine-in-params entries."""
    out = []
    pos = {n: i for i, n in enumerate(iter_names)}
    for ix in acc.resolved():
        row = [Affine.of(0)] * pnest.n_bands
        const = Affine.of(ix.const)
        for sym, c in ix.coeffs:
            if sym in pos:
                d = pos[sym]
                const = const + inst.c[d] * c
                for b in range(pnest.n_bands):
                    row[b] = row[b] + inst.A[d][b] * c
            elif sym in pnest.params:
                const = const + Affine(coeffs=((sym, c),))
            else:
                raise KeyError(
                    f"access symbol {sym!r} is not an iterator or param"
                )
        out.append((tuple(row), const))
    return out


def _affine_traced(aff: Affine, scope: Mapping[str, jnp.ndarray]):
    """Evaluate an Affine whose symbols map to traced int32 scalars.

    Rational coefficients are handled exactly: the whole expression is
    scaled by the lcm of the denominators, evaluated in integers, and
    divided back out — by construction (divisibility constraints) the
    result is integral, so the floor division is exact.
    """
    L = aff.denominator
    acc = jnp.int32(int(aff.const * L))
    for sym, c in aff.coeffs:
        acc = acc + jnp.int32(int(c * L)) * scope[sym]
    return acc // L if L != 1 else acc


# -- parametric strided fast path (dynamic-slice windows) --------------------
#
# The third lowering regime: when the symbolic nest satisfies the same
# precondition as the specialized strided-slice path (single-band affine
# instance maps with constant integer strides, provably unguarded, one
# window dim per access, consistent coefficient signs), lane chunks are
# executed as ``lax.dynamic_slice`` / ``dynamic_update_slice`` windows
# whose starts are computed from the traced extent operands — per-call
# cost tracks the specialized path instead of paying the masked
# gather/scatter tax, so ``programs``-axis sweeps on one executable stay
# regime-comparable.
#
# Window mechanics: windows are **multi-dimensional**. Every
# dynamic-extent band that the write references is a *window band* — the
# innermost (lane) band always, plus, for stencil nests lowered under an
# N-D spec, the outer i/j bands of jacobi2d/3d — and one
# ``lax.dynamic_slice`` covers an (i-chunk x j-chunk x ...) box per loop
# step instead of a row per step. Bands with *static* extents that the
# write references (the independent template's ``programs`` axis) are
# vectorized into the window itself — a ``(programs, Ci, Cj)``-shaped
# dynamic slice per step, so the hot loop matches the specialized path's
# full-width slice ops instead of serializing programs. Dynamic bands
# the write ignores stay serial loop bands (last-value-wins) and
# contribute point (size-1) dims per loop step. The window geometry is
# resolved per ladder by :func:`param_strided_window` into either a
# plain int (rank-1: the legacy lane chunk) or a ``((band, C), ...)``
# spec; ``param_window_bands`` names the candidate bands.
#
# One traced ``fori_loop`` body in one of two emission modes (NEVER a
# ``lax.cond`` between them: XLA:CPU loses buffer aliasing through
# conditionals, which resurrects a capacity-sized copy per call and
# defeats the whole regime):
#
# * ``assume_full`` (drivers emit this whenever they can clamp the
#   chunk to the ladder's smallest window extent — every measurement
#   chunk is then provably full): the final window of a rung is pulled
#   back to ``min(ws, ext - C)`` instead of masked — the overlapped
#   lanes recompute identical values (writes are idempotent), so every
#   lane is a valid point, no masks, no clamped slices, and the write
#   is a plain ``dynamic_update_slice``. Calling this executable at an
#   env with ``ext < C`` is a caller-contract violation.
# * masked (the default; correct for every rung): windows are anchored
#   sign-aware — band range ``[ws, ws+C)`` with the start floored at 0
#   for ascending accesses and allowed to go negative (range
#   ``[ext-C, ext)``) for descending ones, so slice starts stay at
#   valid in-bounds positions even when a rung is smaller than one
#   window — and the write *blends*: lanes outside [0, ext) keep the
#   target's current contents (they may sit in the independent
#   template's pad columns, which the oracle checks).
#
# Strided accesses (|coeff| > 1) use windows of ``(extent-1)*|coeff|+1``
# elements (exactly the strided span) and subsample/blend with static
# strided slices. ``param_strided_in_bounds`` is the exact per-env
# capacity-bounds check drivers run before committing a ladder to this
# regime (a clamped dynamic slice would silently misalign, so any env
# whose windows could leave the capacity shapes falls back to gather),
# and ``param_strided_window`` is the ladder-level (chunk, assume_full)
# policy they resolve it with.


@dataclasses.dataclass(frozen=True)
class ParamStridedPlan:
    """Access-level window plan for the parametric strided regime.

    ``plans[k] = (reads, write, window_sign)`` for instance k; each
    access is a tuple over its array dims of ``(band, stride, const)``
    where ``band`` is the driving band (-1 for a constant index),
    ``stride`` the constant integer coefficient, and ``const`` the
    symbolic offset (Affine in the params). ``window_sign`` is the shared
    coefficient sign of the window band across the instance's accesses
    (sign consistency is part of eligibility), which picks the partial-
    window anchor. ``window_band`` is the nest's innermost band — the one
    lane windows run along.
    """

    window_band: int
    plans: tuple


def param_strided_plan(pattern: PatternSpec,
                       pnest: ParamNest) -> ParamStridedPlan | None:
    """The window plan when (pattern, pnest) admits the strided regime,
    else None (caller falls back to masked gather/scatter).

    On top of :meth:`ParamNest.strided_eligible` (nest-level), every
    access must be sliceable: at most one band per array dim, constant
    integer coefficients, consistent signs per band across an instance's
    accesses, the write referencing the window band, and no access
    referencing it in more than one dim (diagonals stay on gather).
    Statements that read their own write space are rejected outright —
    the min-start window overlap recomputes the final lanes of a rung,
    and a re-read of already-updated values would corrupt them (the
    serial oracle's vectorized path guards the same case).
    """
    if pattern.kernel is not None or not pnest.strided_eligible():
        return None
    stmt = pattern.statement
    if any(a.space == stmt.write.space for a in stmt.reads):
        return None
    iter_names = pattern.domain.names
    w = pnest.n_bands - 1
    zero = Affine.of(0)
    insts = []
    for inst in pnest.instances:
        try:
            raccs = [resolve_access_symbolic(a, pnest, inst, iter_names)
                     for a in stmt.reads]
            wacc = resolve_access_symbolic(stmt.write, pnest, inst, iter_names)
        except KeyError:
            return None
        sign: dict[int, int] = {}

        def conv(rows):
            out, seen = [], set()
            for row, const in rows:
                nz = [(b, _const_int(c)) for b, c in enumerate(row)
                      if c != zero]
                if not nz:
                    out.append((-1, 0, const))
                    continue
                if len(nz) > 1:
                    return None
                b, cf = nz[0]
                if cf is None or cf == 0:
                    return None
                s = 1 if cf > 0 else -1
                if sign.setdefault(b, s) != s:
                    return None
                if b in seen:  # diagonal (one band, two dims): gather
                    return None
                seen.add(b)
                out.append((b, cf, const))
            return tuple(out)

        w_conv = conv(wacc)
        if w_conv is None or not any(b == w for b, _, _ in w_conv):
            return None
        r_convs = []
        for rows in raccs:
            rc = conv(rows)
            if rc is None:
                return None
            r_convs.append(rc)
        insts.append((tuple(r_convs), w_conv, sign.get(w, 1)))
    return ParamStridedPlan(window_band=w, plans=tuple(insts))


def _static_extents(pnest: ParamNest) -> dict[int, int]:
    """Bands whose extents are parameter-free: candidates for window
    vectorization (the independent template's ``programs`` axis)."""
    out = {}
    for b, e in enumerate(pnest.band_extents):
        v = _const_int(e)
        if v is not None and v > 0:
            out[b] = v
    return out


def _vector_bands(splan: ParamStridedPlan, static_ext: Mapping[int, int],
                  ) -> tuple[int, ...]:
    """Static-extent bands every instance's write references: these are
    folded into the window shape instead of the chunk loop (all their
    points execute per step, so the write must cover them — a band the
    write ignores must stay serial for last-value-wins semantics)."""
    vec = set(static_ext)
    for _, wacc, _ in splan.plans:
        vec &= {b for b, _, _ in wacc if b >= 0}
    return tuple(sorted(vec))


def param_window_bands(pnest: ParamNest,
                       splan: ParamStridedPlan) -> tuple[int, ...]:
    """Ordered (outer -> inner) window-band candidates of the strided
    regime: every *dynamic*-extent band that the write of every instance
    references — the dims an N-D dynamic window may span — always ending
    with the innermost lane band. Dynamic bands the write ignores must
    stay serial loop bands (a window over them would collapse their
    last-value-wins writes), and static-extent bands are vectorized into
    the window shape instead (see :func:`_vector_bands`)."""
    static = _static_extents(pnest)
    cands = set(range(pnest.n_bands)) - set(static)
    for _, wacc, _ in splan.plans:
        cands &= {b for b, _, _ in wacc if b >= 0}
    cands.add(splan.window_band)
    return tuple(sorted(cands))


def _window_chunks(pnest: ParamNest, splan: ParamStridedPlan,
                   cap_env: Mapping[str, int], chunk,
                   ) -> tuple[tuple[int, ...], dict[int, int]]:
    """Normalize a window spec into ``(window bands, {band: chunk})``.

    An int is the legacy rank-1 form: the lane band alone is windowed
    (clamped to the capacity extent) and every other dynamic band loops.
    A ``((band, C), ...)`` tuple is the explicit N-D geometry the ladder
    policy (:func:`param_strided_window`) resolved — pairs in band
    order, ending with the lane band. All three window consumers (the
    jax emitter, the numpy mirror, the bounds check) normalize through
    here, so their geometry can never drift apart.
    """
    w = splan.window_band
    if isinstance(chunk, (tuple, list)):
        bands = tuple(int(b) for b, _ in chunk)
        if not bands or bands[-1] != w or list(bands) != sorted(set(bands)):
            raise ValueError(
                f"window spec {tuple(chunk)!r} must list distinct "
                f"(band, chunk) pairs in band order ending with the lane "
                f"band {w}"
            )
        return bands, {int(b): max(1, int(c)) for b, c in chunk}
    cap_ext_w = max(1, pnest.band_extents[w].eval(cap_env))
    return (w,), {w: int(min(chunk, cap_ext_w))}


class _WindowPlan:
    """Shared window geometry for the jax emitter and its numpy mirror.

    Splits bands into ``wins`` — the window bands (dynamic extents,
    chunked; the innermost lane band ``w`` always, plus any outer
    dynamic bands an N-D spec promotes) — ``vec`` bands (static extents,
    vectorized into each window) and ``loop`` bands (everything else —
    one point per chunk step). ``spec(rows, ws, ob)`` computes per-dim
    dynamic-slice starts/sizes plus the static lane selector and per-dim
    band tags for one access, with ``ws`` mapping each window band to
    its traced start.
    """

    def __init__(self, pnest: ParamNest, splan: ParamStridedPlan,
                 wins: tuple[int, ...], chunks: Mapping[int, int]):
        self.w = splan.window_band
        self.wins = tuple(wins)
        self.Cs = {int(b): int(chunks[b]) for b in wins}
        self.C = self.Cs[self.w]
        self.static_ext = _static_extents(pnest)
        self.vec = tuple(
            b for b in _vector_bands(splan, self.static_ext)
            if b not in self.Cs
        )
        self.loop = tuple(
            b for b in range(pnest.n_bands)
            if b not in self.Cs and b not in self.vec
        )

    def lane_extent(self, b: int) -> int:
        return self.Cs[b] if b in self.Cs else self.static_ext[b]

    def spec(self, rows, ws, ob):
        """(starts, sizes, selector, per-dim band-or-None) for one access
        at window starts ``ws`` (band -> start) / loop-band coords ``ob``."""
        starts, sizes, sel, axes = [], [], [], []
        for b, cf, kc in rows:
            if b in self.Cs or b in self.vec:
                e = self.lane_extent(b)
                base = ws[b] if b in self.Cs else 0
                if cf > 0:
                    starts.append(cf * base + kc)
                else:
                    starts.append(cf * (base + (e - 1)) + kc)
                sizes.append((e - 1) * abs(cf) + 1)
                sel.append(slice(None, None, cf))
                axes.append(b)
            elif b >= 0:
                starts.append(cf * ob[b] + kc)
                sizes.append(1)
                sel.append(slice(None))
                axes.append(None)
            else:
                starts.append(kc)
                sizes.append(1)
                sel.append(slice(None))
                axes.append(None)
        return starts, sizes, tuple(sel), axes

    def align(self, waxes):
        """Return ``fit(v, raxes)`` mapping a read's lane value onto the
        write's dim layout: banded axes permuted into the write's band
        order, point axes squeezed, missing bands broadcast as size 1."""
        worder = [b for b in waxes if b is not None]
        wshape_of = {b: self.lane_extent(b) for b in worder}

        def fit(xp, v, raxes):
            perm = [d for b in worder for d, rb in enumerate(raxes)
                    if rb == b]
            perm += [d for d, rb in enumerate(raxes) if rb is None]
            if perm != list(range(len(raxes))):
                v = xp.transpose(v, tuple(perm))
            have = {rb for rb in raxes if rb is not None}
            tshape = tuple(
                wshape_of[b] if (b is not None and b in have) else 1
                for b in waxes
            )
            return v.reshape(tshape)

        return fit


def _read_hulls(stmt, racc_sym):
    """Group an instance's reads into per-space *hull* windows.

    Stencil statements read the same space at several constant offsets
    (``B[i-1], B[i], B[i+1]``). Slicing each one dynamically costs a
    materialized temporary per read; the specialized path instead takes
    static slices of one array, which XLA fuses. The hull is the
    parametric analogue: reads that agree on ``(band, stride)`` per dim
    and differ only by *constant* index offsets share one dynamic slice
    of their union span (the halo'd window), and each member becomes a
    static subslice of the hull — same elements, same values, one
    dynamic op per space.

    Returns ``[(space, hull_rows, spans, members), ...]`` where
    ``hull_rows`` are symbolic ``(band, stride, const)`` rows at the
    hull's minimal offset, ``spans[d]`` is the extra static extent the
    union adds per dim, and ``members`` maps each original read index to
    its static offsets inside the hull.
    """
    groups: list[dict] = []
    for ridx, (acc, rows) in enumerate(zip(stmt.reads, racc_sym)):
        placed = False
        for g in groups:
            if g["space"] != acc.space or len(g["rows"]) != len(rows):
                continue
            deltas = []
            for (b0, cf0, k0), (b, cf, kc) in zip(g["rows"], rows):
                if b != b0 or cf != cf0:
                    deltas = None
                    break
                dv = _const_int(Affine.of(kc - k0))
                if dv is None:
                    deltas = None
                    break
                deltas.append(dv)
            if deltas is not None:
                g["members"].append((ridx, tuple(deltas)))
                placed = True
                break
        if not placed:
            groups.append({
                "space": acc.space,
                "rows": tuple(rows),
                "members": [(ridx, (0,) * len(rows))],
            })
    out = []
    for g in groups:
        rank = len(g["rows"])
        lo = [min(d[i] for _, d in g["members"]) for i in range(rank)]
        hi = [max(d[i] for _, d in g["members"]) for i in range(rank)]
        hull_rows = tuple(
            (b, cf, kc + l) for (b, cf, kc), l in zip(g["rows"], lo)
        )
        spans = tuple(h - l for l, h in zip(lo, hi))
        members = tuple(
            (ridx, tuple(d - l for d, l in zip(deltas, lo)))
            for ridx, deltas in g["members"]
        )
        out.append((g["space"], hull_rows, spans, members))
    return out


def param_strided_window(
    pnest: ParamNest, splan: ParamStridedPlan,
    envs: "list[Mapping[str, int]]", cap_env: Mapping[str, int],
    chunk: int = _PARAM_CHUNK, floor: int = 1024,
) -> "tuple[int | tuple, bool]":
    """The ladder-level window policy: ``(window_spec, assume_full)``.

    Rank 1 (the lane band is the only windowable dynamic band): the
    PR-4 policy — when the smallest rung's window extent is at least
    ``floor`` lanes, the chunk is clamped down to it, so every chunk of
    every rung is provably full and the emitter skips masks and blend
    reads entirely (the hot mode); ladders with tinier rungs take the
    masked emission mode instead.  Masked mode gets a second clamp
    tier: the lane chunk is bounded by ``max(floor, smallest rung
    extent)`` rather than the capacity extent, so the per-chunk masked
    work scales with the rung being measured (the runtime trip count
    ``ceil(extent / chunk)`` does the rest) instead of every rung
    paying a capacity-sized blend.  The spec stays a plain int.

    Rank >= 2 (outer dynamic bands the write references — stencil
    nests): the spec is a ``((band, C), ...)`` tuple. Outer window
    bands are clamped to the ladder's smallest rung extent, so their
    windows are provably full at every declared env (min-start overlap,
    never a mask; an outer band some rung zeroes out is left as a loop
    band). The lane band joins the mask-free mode when the smallest
    rung's whole window — window-band chunks times vectorized static
    extents — carries at least ``floor`` points (an N-D window is big
    even when each per-band chunk is small); otherwise it takes the
    sign-anchored masked emission with the same second-tier lane clamp
    (``max(floor, smallest rung extent)``, never the capacity). The
    ``chunk`` budget bounds the window's total dynamic-lane count,
    distributed innermost-first.
    """
    w = splan.window_band
    cap_scope = {k: int(v) for k, v in cap_env.items()}
    scopes = [{**cap_scope, **{k: int(v) for k, v in e.items()}}
              for e in envs]
    bands = param_window_bands(pnest, splan)
    m = {
        b: (min(max(0, pnest.band_extents[b].eval(s)) for s in scopes)
            if scopes else 0)
        for b in bands
    }
    cap_ext_w = max(1, pnest.band_extents[w].eval(cap_env))
    outer = [b for b in bands[:-1] if m[b] >= 1]
    masked_cw = int(min(chunk, cap_ext_w, max(floor, m[w])))
    if not outer:
        if m[w] >= floor:
            return int(min(chunk, m[w], cap_ext_w)), True
        return masked_cw, False
    static_ext = _static_extents(pnest)
    lanes = max(0, m[w])
    for b in outer:
        lanes *= m[b]
    for b in _vector_bands(splan, static_ext):
        if b not in bands:
            lanes *= static_ext[b]
    full = lanes >= floor and m[w] >= 1
    cw = int(min(chunk, m[w], cap_ext_w)) if full else masked_cw
    spec = [(w, max(1, cw))]
    used = max(1, cw)
    for b in reversed(outer):
        cb = int(max(1, min(m[b], chunk // used)))
        spec.append((b, cb))
        used *= cb
    return tuple(sorted(spec)), full


def param_strided_in_bounds(
    pattern: PatternSpec, pnest: ParamNest, splan: ParamStridedPlan,
    env: Mapping[str, int], cap_env: Mapping[str, int],
    chunk: "int | tuple" = _PARAM_CHUNK,
) -> bool:
    """Exact check that every window the strided step could slice at
    ``env`` stays inside the capacity-allocated shapes.

    ``lax.dynamic_slice`` silently clamps out-of-range starts, which
    would *misalign* a window rather than fail — so drivers verify every
    ladder point here before choosing the strided regime, and any unsafe
    env demotes its whole ladder to the gather regime. ``chunk`` is the
    resolved window spec (int or N-D tuple — see
    :func:`_window_chunks`); for N-D specs every window band's anchor
    range is checked, including the negative start an outer band smaller
    than its chunk would take. Real patterns (spans scaling with the
    working set) always pass; the check guards hand-built specs with
    fixed-size spaces and mis-sized ladders.
    """
    stmt = pattern.statement
    w = splan.window_band
    scope = {**{k: int(v) for k, v in cap_env.items()},
             **{k: int(v) for k, v in env.items()}}
    try:
        ext = [max(0, e.eval(scope)) for e in pnest.band_extents]
    except (KeyError, ValueError):
        return False
    wins, Cs = _window_chunks(pnest, splan, cap_env, chunk)
    if any(ext[b] < 1 for b in wins):
        return True  # a zero-extent window band: the trip count is 0
    static_ext = _static_extents(pnest)
    shapes = {s.name: s.concrete_shape(cap_env) for s in pattern.spaces}
    for racc, wacc, s_w in splan.plans:
        anchors = {}
        for b in wins:
            C = Cs[b]
            if ext[b] >= C:
                anchors[b] = (0, ext[b] - 1)
            elif b == w:
                # lane partial-window anchor: [0, C) ascending,
                # [ext-C, ext) descending
                anchors[b] = ((0, C - 1) if s_w > 0
                              else (ext[b] - C, ext[b] - 1))
            else:
                # outer windows are always full-anchored: a rung smaller
                # than its chunk starts at ext-C < 0 (and is demoted)
                anchors[b] = (ext[b] - C, ext[b] - 1)
        for acc, rows in zip((*stmt.reads, stmt.write), (*racc, wacc)):
            dims = shapes[acc.space]
            for d, (b, cf, kc) in enumerate(rows):
                try:
                    k = kc.eval(scope)
                except (KeyError, ValueError):
                    return False
                if b in anchors:
                    lo, hi = anchors[b]
                elif b in static_ext:
                    lo, hi = 0, static_ext[b] - 1
                elif b >= 0:
                    lo, hi = 0, max(0, ext[b] - 1)
                else:
                    lo = hi = 0
                pts = (k + cf * lo, k + cf * hi)
                if min(pts) < 0 or max(pts) > dims[d] - 1:
                    return False
    return True


def _lower_param_strided(pattern: PatternSpec, pnest: ParamNest,
                         splan: ParamStridedPlan,
                         params: tuple[str, ...],
                         cap_env: Mapping[str, int], chunk,
                         assume_full: bool = False) -> Callable:
    """Emit the windowed step: same calling convention as the gather
    parametric step (capacity-shaped arrays + traced param scalars).

    ``assume_full=True`` emits the mask-free hot mode; the caller must
    only invoke the step at envs whose window extent is >= the chunk
    (drivers guarantee this via :func:`param_strided_window`).
    """
    stmt = pattern.statement
    w = splan.window_band
    wins, Cs = _window_chunks(pnest, splan, cap_env, chunk)
    C = Cs[w]
    rest_env = {k: int(v) for k, v in cap_env.items() if k not in params}
    wp = _WindowPlan(pnest, splan, wins, Cs)
    outer_wins = wins[:-1]
    # per instance: reads fused into per-space hull windows (one dynamic
    # slice per space, static subslices per stencil offset — see
    # _read_hulls), resolved symbolically once at lower time
    grouped = [
        (_read_hulls(stmt, racc), wacc, s_w)
        for racc, wacc, s_w in splan.plans
    ]

    def step(arrays: dict[str, jnp.ndarray], pvals) -> dict[str, jnp.ndarray]:
        arrays = dict(arrays)
        scope = {p: jnp.asarray(v, jnp.int32) for p, v in zip(params, pvals)}
        cenv = {**rest_env, **scope}
        ext = [jnp.maximum(_affine_traced(e, scope), 0)
               for e in pnest.band_extents]
        ext_w = ext[w]
        nw = {b: (ext[b] + (Cs[b] - 1)) // Cs[b] for b in wins}
        win_lo = {b: ext[b] - Cs[b] for b in wins}
        # mixed-radix trip space: serial loop bands outermost, window
        # bands (outer -> inner) innermost, so the lane band varies
        # fastest — identical decomposition to the numpy mirror
        radii = [(b, ext[b]) for b in wp.loop] + [(b, nw[b]) for b in wins]
        strides = {}
        total = jnp.int32(1)
        for b, r in reversed(radii):
            strides[b] = total
            total = total * r
        # loop-invariant traced offsets, computed once outside the body
        tr = [
            (
                [
                    (space,
                     [(b, cf, _affine_traced(kc, scope))
                      for b, cf, kc in hull_rows],
                     spans, members)
                    for space, hull_rows, spans, members in groups
                ],
                [(b, cf, _affine_traced(kc, scope)) for b, cf, kc in wacc],
                s_w,
            )
            for groups, wacc, s_w in grouped
        ]
        lane = (None if assume_full
                else jax.lax.broadcasted_iota(jnp.int32, (C,), 0))

        def instance(arrs, groups, wacc, ws, ob, valid):
            """One instance's window step at window starts ``ws`` (band
            -> start); lanes where ``valid`` is False (masked lane mode
            only) keep the target's current contents."""
            wstarts, wsizes, wsel, waxes = wp.spec(wacc, ws, ob)
            fit = wp.align(waxes)
            vals: list = [None] * len(stmt.reads)
            with jax.named_scope(SCOPE_READ):
                for space, hull_rows, spans, members in groups:
                    starts, sizes, sel, raxes = wp.spec(hull_rows, ws, ob)
                    hsizes = [s + sp for s, sp in zip(sizes, spans)]
                    hull = jax.lax.dynamic_slice(arrs[space], starts,
                                                 hsizes)
                    for ridx, offs in members:
                        sub = hull[tuple(
                            slice(o, o + s) for o, s in zip(offs, sizes)
                        )]
                        vals[ridx] = fit(jnp, sub[sel], raxes)
            tgt = arrs[stmt.write.space]
            with jax.named_scope(SCOPE_COMBINE):
                res = stmt.combine(vals, cenv)
                lanes = tuple(
                    wp.lane_extent(b) if b is not None else 1 for b in waxes
                )
                res = jnp.broadcast_to(jnp.asarray(res).astype(tgt.dtype),
                                       lanes)
            with jax.named_scope(SCOPE_WRITE):
                if valid is None and all(cf == 1 for b, cf, _ in wacc
                                         if b >= 0):
                    return jax.lax.dynamic_update_slice(tgt, res, wstarts)
                # strided / reversed / masked write: blend into the
                # window so gap elements and invalid lanes stay untouched
                win = jax.lax.dynamic_slice(tgt, wstarts, wsizes)
                if valid is not None:
                    vshape = tuple(C if b == w else 1 for b in waxes)
                    res = jnp.where(valid.reshape(vshape), res, win[wsel])
                win = win.at[wsel].set(res)
                return jax.lax.dynamic_update_slice(tgt, win, wstarts)

        def body(ci, arrs):
            arrs = dict(arrs)
            idx = {b: (ci // strides[b]) % r for b, r in radii}
            ob = {b: idx[b] for b in wp.loop}
            # outer window bands always take full windows: their chunks
            # are clamped to the ladder's smallest rung, so the min-
            # start overlap keeps every slice in bounds with no masks
            ws0 = {b: jnp.minimum(idx[b] * Cs[b], win_lo[b])
                   for b in outer_wins}
            wsq = idx[w] * C
            for groups, wacc, s_w in tr:
                if assume_full:
                    # every lane chunk is a full window too: min-start
                    # overlap, no masks (caller guarantees ext_w >= C)
                    ws = dict(ws0)
                    ws[w] = jnp.minimum(wsq, win_lo[w])
                    arrs[stmt.write.space] = instance(
                        arrs, groups, wacc, ws, ob, None)
                    continue
                # sign-aware lane anchor: ascending accesses floor the
                # start at 0, descending ones let it go negative so the
                # partial window sits at [ext-C, ext) — either way slice
                # starts stay at valid positions
                wsl = jnp.minimum(wsq, win_lo[w])
                if s_w > 0:
                    wsl = jnp.maximum(wsl, 0)
                band = wsl + lane
                valid = (band >= 0) & (band < ext_w)
                ws = dict(ws0)
                ws[w] = wsl
                arrs[stmt.write.space] = instance(
                    arrs, groups, wacc, ws, ob, valid)
            return arrs

        return jax.lax.fori_loop(0, total, body, arrays)

    step.param_window_rank = len(wins)
    return step


def windowed_oracle(
    pattern: PatternSpec, schedule: Schedule, env: Mapping[str, int],
    cap_env: Mapping[str, int], arrays: dict[str, np.ndarray],
    ntimes: int = 1, *, params: tuple[str, ...] = ("n",),
    chunk: "int | tuple" = _PARAM_CHUNK, assume_full: bool = False,
) -> dict[str, np.ndarray]:
    """Numpy mirror of the parametric strided regime, window for window.

    Replays the exact chunk decomposition (vectorized static bands,
    N-D window boxes with per-band min-start overlap, sign-aware
    partial-window anchors, strided subsampling, blend writes, tail-lane
    masking) on capacity-shaped numpy arrays, so tests can prove the
    window arithmetic against plain semantics — bit-for-bit against the
    jax step over the *whole* capacity arrays, not just the [0, n)
    region — without tracing. ``chunk`` accepts the same int / N-D
    ``((band, C), ...)`` window specs as the jax emitter and mirrors
    whichever geometry it names. Raises when (pattern, schedule) is not
    strided-eligible.
    """
    pnest = schedule.lower_symbolic(pattern.domain, tuple(params))
    splan = param_strided_plan(pattern, pnest)
    if splan is None:
        raise ValueError(
            f"pattern {pattern.name!r} / schedule {schedule.name!r} is not "
            "strided-eligible; the windowed mirror has nothing to replay"
        )
    stmt = pattern.statement
    w = splan.window_band
    scope = {**{k: int(v) for k, v in cap_env.items()
                if k not in params},
             **{p: int(env[p]) for p in params}}
    ext = [max(0, e.eval(scope)) for e in pnest.band_extents]
    ext_w = ext[w]
    wins, Cs = _window_chunks(pnest, splan, cap_env, chunk)
    C = Cs[w]
    wp = _WindowPlan(pnest, splan, wins, Cs)
    outer_wins = wins[:-1]
    nw = {b: -(-ext[b] // Cs[b]) if ext[b] else 0 for b in wins}
    radii = [(b, ext[b]) for b in wp.loop] + [(b, nw[b]) for b in wins]
    strides = {}
    total = 1
    for b, r in reversed(radii):
        strides[b] = total
        total = total * int(r)
    arrays = {k: np.array(v) for k, v in arrays.items()}
    plans = [
        (
            [[(b, cf, kc.eval(scope)) for b, cf, kc in rows] for rows in racc],
            [(b, cf, kc.eval(scope)) for b, cf, kc in wacc],
            s_w,
        )
        for racc, wacc, s_w in splan.plans
    ]
    for _ in range(int(ntimes)):
        for ci in range(int(total)):
            idx = {b: (ci // strides[b]) % int(r) for b, r in radii}
            ob = {b: idx[b] for b in wp.loop}
            ws0 = {b: min(idx[b] * Cs[b], ext[b] - Cs[b])
                   for b in outer_wins}
            wsq = idx[w] * C
            for racc, wacc, s_w in plans:
                ws = dict(ws0)
                if assume_full:
                    ws[w], valid = min(wsq, ext_w - C), None
                else:
                    wsl = min(wsq, ext_w - C)
                    if s_w > 0:
                        wsl = max(wsl, 0)
                    ws[w] = wsl
                    band = wsl + np.arange(C)
                    valid = (band >= 0) & (band < ext_w)
                wstarts, wsizes, wsel, waxes = wp.spec(wacc, ws, ob)
                fit = wp.align(waxes)
                vals = []
                for acc, rows in zip(stmt.reads, racc):
                    starts, sizes, sel, raxes = wp.spec(rows, ws, ob)
                    win = arrays[acc.space][tuple(
                        slice(s, s + z) for s, z in zip(starts, sizes))]
                    vals.append(fit(np, np.asarray(win[sel]), raxes))
                res = stmt.combine(vals, dict(scope))
                tgt = arrays[stmt.write.space]
                lanes = tuple(
                    wp.lane_extent(b) if b is not None else 1 for b in waxes
                )
                res = np.broadcast_to(
                    np.asarray(res).astype(tgt.dtype), lanes)
                osel = tuple(
                    slice(s, s + z) for s, z in zip(wstarts, wsizes))
                win = np.array(tgt[osel])
                if valid is not None:
                    vshape = tuple(C if b == w else 1 for b in waxes)
                    res = np.where(valid.reshape(vshape), res, win[wsel])
                win[wsel] = res
                tgt[osel] = win
    return arrays


def lower_jax_parametric(
    pattern: PatternSpec, schedule: Schedule, cap_env: Mapping[str, int],
    *, params: tuple[str, ...] = ("n",), chunk: "int | tuple" = _PARAM_CHUNK,
    pnest: ParamNest | None = None, param_path: str = "auto",
    assume_full: bool = False,
) -> Callable:
    """Build ``step(arrays, pvals) -> arrays`` with the working-set
    parameter(s) as *traced operands* instead of baked constants.

    One executable serves every working set up to the capacity
    ``cap_env`` (arrays are allocated at capacity shapes): band extents,
    instance maps, and domain bounds are computed inside the trace from
    the ``pvals`` scalars, and points are executed in fixed-shape lane
    chunks under a dynamic trip count (``fori_loop`` over
    ``ceil(points/chunk)``), so the work a call performs scales with the
    *runtime* working set — a ladder shares one compiled program without
    every rung paying capacity-sized sweeps.

    ``param_path`` picks the lowering regime: ``"auto"`` prefers the
    strided fast path (dynamic-slice windows — see
    :func:`param_strided_plan`) and falls back to masked gather/scatter;
    ``"strided"`` requires the fast path (raises
    :class:`~repro.core.schedule.SymbolicLowerError` when ineligible);
    ``"gather"`` pins the masked form (the reference regime the tests
    compare against). The returned step carries the chosen regime as
    ``step.param_path`` and its window dimensionality as
    ``step.param_window_rank`` (0 on the gather path). On the strided
    path, ``chunk`` is either a lane-chunk int (rank-1 windows, outer
    dynamic bands loop serially) or a ``((band, C), ...)`` N-D window
    spec from :func:`param_strided_window` (stencil nests window an
    (i-chunk x j-chunk x ...) box per step). ``assume_full`` selects the
    strided emitter's mask-free hot mode — only valid when every env the
    step will run satisfies ``lane window extent >= lane chunk`` (outer
    N-D window bands are clamped by the ladder policy, so they are
    always full).

    Caller contract of the strided regime: every env the step runs must
    pass :func:`param_strided_in_bounds` — a window that leaves the
    capacity shapes is silently *clamped* by ``lax.dynamic_slice``, i.e.
    misaligned, not an error. ``Driver`` verifies this per ladder before
    choosing the regime; direct users of this function (with patterns
    whose spaces do not scale with the working set) must check it
    themselves or pin ``param_path="gather"``, which is safe at every
    env that ``ParamNest.admits``.

    On the gather path, reads and the write are gather/scatter over the
    chunk lanes; lanes past the dynamic point count (or outside the
    domain, for guarded nests) are masked onto index -1 and dropped,
    mirroring the specialized gather path. Preconditions checked by the
    caller via ``ParamNest.admits``: every requested env must satisfy
    the nest's divisibility constraints.
    """
    from .schedule import SymbolicLowerError

    if param_path not in ("auto", "strided", "gather"):
        raise ValueError(f"unknown param_path {param_path!r}")
    if pattern.kernel is not None:
        raise SymbolicLowerError(
            f"pattern {pattern.name!r} has a custom kernel; the parametric "
            "path cannot share it (env is baked into the step)"
        )
    if pnest is None:
        pnest = schedule.lower_symbolic(pattern.domain, params)
    splan = (param_strided_plan(pattern, pnest)
             if param_path != "gather" else None)
    if param_path == "strided" and splan is None:
        raise SymbolicLowerError(
            f"pattern {pattern.name!r} under schedule {schedule.name!r} is "
            "not strided-eligible (single-band constant-stride unguarded "
            "nests only); use param_path='auto' to fall back to gather"
        )
    if splan is not None:
        step = _lower_param_strided(
            pattern, pnest, splan, tuple(params), cap_env, chunk,
            assume_full=assume_full,
        )
        step.param_path = "strided"
        return step
    if not isinstance(chunk, int):
        # an N-D window spec only means something to the strided
        # emitter; the gather fallback keeps its default lane chunk
        chunk = _PARAM_CHUNK
    stmt = pattern.statement
    iter_names = pattern.domain.names
    plans = tuple(
        (
            tuple(
                resolve_access_symbolic(a, pnest, inst, iter_names)
                for a in stmt.reads
            ),
            resolve_access_symbolic(stmt.write, pnest, inst, iter_names),
        )
        for inst in pnest.instances
    )
    n_bands = pnest.n_bands
    rank = pnest.rank
    cap_extents = tuple(max(0, e.eval(cap_env)) for e in pnest.band_extents)
    cap_pts = int(np.prod(cap_extents)) if cap_extents else 1
    if cap_pts > _GATHER_POINT_CAP:
        raise ValueError(
            f"parametric path would stage {cap_pts} capacity points; "
            "use lower_pallas"
        )
    C = int(min(chunk, max(1, cap_pts)))
    rest_env = {k: int(v) for k, v in cap_env.items() if k not in params}

    def step(arrays: dict[str, jnp.ndarray], pvals) -> dict[str, jnp.ndarray]:
        arrays = dict(arrays)
        scope = {p: jnp.asarray(v, jnp.int32) for p, v in zip(params, pvals)}
        cenv = {**rest_env, **scope}

        ext = [jnp.maximum(_affine_traced(e, scope), 0)
               for e in pnest.band_extents]
        strides = [None] * n_bands
        s = jnp.int32(1)
        for b in reversed(range(n_bands)):
            strides[b] = s
            s = s * ext[b]
        npts = s if n_bands else jnp.int32(1)
        nchunks = (npts + (C - 1)) // C
        lane0 = jax.lax.broadcasted_iota(jnp.int32, (C,), 0)
        lo = [_affine_traced(l, scope) for l in pnest.domain_lo]
        hi = [_affine_traced(h, scope) for h in pnest.domain_hi]
        # loop-invariant scalar coefficients, computed once outside the body
        tr_plans = [
            (
                [
                    [
                        ([_affine_traced(cf, scope) for cf in row],
                         _affine_traced(const, scope))
                        for row, const in rows
                    ]
                    for rows in racc
                ],
                [
                    ([_affine_traced(cf, scope) for cf in row],
                     _affine_traced(const, scope))
                    for row, const in wacc
                ],
                [
                    ([_affine_traced(cf, scope) for cf in inst.A[d]],
                     _affine_traced(inst.c[d], scope))
                    for d in range(rank)
                ],
            )
            for (racc, wacc), inst in zip(plans, pnest.instances)
        ]

        def body(ci, arrs):
            arrs = dict(arrs)
            lanes = ci * C + lane0
            valid0 = lanes < npts
            cols = [(lanes // strides[b]) % ext[b] for b in range(n_bands)]

            def lin(coeffs, const):
                acc = jnp.full((C,), 1, jnp.int32) * const
                for b, cf in enumerate(coeffs):
                    acc = acc + cf * cols[b]
                return acc

            for racc, wacc, imap in tr_plans:
                valid = valid0
                for d in range(rank):
                    it = lin(*imap[d])
                    valid = valid & (it >= lo[d]) & (it < hi[d])
                vals = [
                    arrs[acc.space][tuple(lin(*rc) for rc in rows)]
                    for acc, rows in zip(stmt.reads, racc)
                ]
                res = stmt.combine(vals, cenv)
                tgt = arrs[stmt.write.space]
                widx = tuple(
                    jnp.where(valid, lin(*rc), -1) for rc in wacc
                )
                arrs[stmt.write.space] = tgt.at[widx].set(
                    jnp.asarray(res).astype(tgt.dtype), mode="drop"
                )
            return arrs

        return jax.lax.fori_loop(0, nchunks, body, arrays)

    step.param_path = "gather"
    step.param_window_rank = 0
    return step


# ---------------------------------------------------------------------------
# Pallas backend (manual-DMA style; blocked showcase kernels in repro.kernels)
# ---------------------------------------------------------------------------


_PALLAS_MODE: dict[str, str] = {}

# The generic emitters below pass no BlockSpecs, so every operand sits
# whole in VMEM for the kernel's lifetime. Compiled for a described TPU
# v5e, three f32 operands of 16 MiB each are accepted and three of 17 MiB
# are refused (RESOURCE_EXHAUSTED in vmem): the budget is 48 MiB.
PALLAS_VMEM_BUDGET = 48 << 20


def pallas_platform_mode() -> str:
    """How ``pl.pallas_call`` executes on the default jax backend.

    ``"interpret"`` on the CPU backend (XLA:CPU cannot lower Pallas
    natively), ``"compiled"`` everywhere else. On an accelerator a probe
    kernel is compiled and run once; when it fails, the compiler's error
    propagates — a device must never silently time the interpreter.
    Memoized per process: translation-cache keys, journal fingerprints
    and every measurement record embed the result (``extra.pallas_mode``).
    """
    mode = _PALLAS_MODE.get("mode")
    if mode is None:
        if jax.default_backend() == "cpu":
            mode = "interpret"
        else:
            def _probe(x_ref, o_ref):
                o_ref[...] = x_ref[...] + 1.0

            call = pl.pallas_call(
                _probe, out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32))
            jax.block_until_ready(
                jax.jit(call)(jnp.zeros((8, 128), jnp.float32)))
            mode = "compiled"
        _PALLAS_MODE["mode"] = mode
    return mode


def _resolve_pallas_mode(mode: str | None) -> str:
    if mode in ("compiled", "interpret"):
        return mode
    return pallas_platform_mode()


def _vmem_preflight(pattern: PatternSpec, shapes: Mapping[str, tuple],
                    dtypes: Mapping[str, str]) -> None:
    """Refuse, before Mosaic sees it, a kernel whose whole operands
    exceed :data:`PALLAS_VMEM_BUDGET`."""
    need = sum(int(np.prod(shapes[k])) * np.dtype(dtypes[k]).itemsize
               for k in shapes)
    if need > PALLAS_VMEM_BUDGET:
        raise LowerFailure(
            f"pattern {pattern.name!r}: the pallas emitter holds every "
            f"operand whole in VMEM; they take {need} bytes, over the "
            f"{PALLAS_VMEM_BUDGET}-byte budget",
            context={"backend": "pallas", "reason": "vmem",
                     "vmem_bytes": need, "budget_bytes": PALLAS_VMEM_BUDGET},
        )


def lower_pallas(
    pattern: PatternSpec, schedule: Schedule, env: Mapping[str, int],
    *, mode: str | None = None,
    grid_bands: tuple[str, ...] | None = None,
    plan: NestPlan | None = None,
) -> Callable[[dict[str, jnp.ndarray]], dict[str, jnp.ndarray]]:
    """Lower to ``pl.pallas_call``.

    Bands are split into *grid bands* (pallas grid) and *vector bands*
    (in-kernel slice extents). By default the innermost unit-stride band
    of each domain dim is the vector band; ``grid_bands`` forces named
    bands into the grid (used by the tile-sweep benchmarks so tile loops
    become grid steps, exactly like the generated ISCC tile loops).
    The output space is aliased to its input so un-iterated elements
    (stencil borders) keep their initial values, matching the oracle.

    ``mode`` selects ``"compiled"`` (native ``pl.pallas_call`` lowering)
    or ``"interpret"``; ``None`` resolves via :func:`pallas_platform_mode`
    (the interpreter on XLA:CPU only). The built step reports the
    resolved mode as ``step.pallas_mode``.

    Refusals (custom kernels, guarded schedules, operands over the VMEM
    budget) raise :class:`~repro.core.errors.LowerFailure` with
    structured context naming the backend and reason, so sweep
    ``FailureRecord``s classify them instead of carrying a bare
    exception string.
    """
    mode = _resolve_pallas_mode(mode)
    if pattern.kernel is not None:
        raise LowerFailure(
            f"pattern {pattern.name!r} has a custom (jax) kernel; "
            "the pallas backend cannot lower it",
            context={"backend": "pallas", "reason": "custom_kernel"},
        )
    if plan is None:
        plan = plan_nest(pattern, schedule, env)
    nest = plan.nest
    if plan.guarded:
        raise LowerFailure(
            "guarded schedules on the pallas backend: pick divisible tile "
            "sizes (the drivers choose divisible working sets)",
            context={"backend": "pallas", "reason": "guarded_schedule"},
        )
    stmt = pattern.statement
    rank = nest.rank

    inst0 = nest.instances[0]
    vec_band_for_dim: list[int] = []
    for d in range(rank):
        cands = [b for b, c in enumerate(inst0.A[d]) if abs(c) == 1]
        if not cands:
            raise LowerFailure(
                f"dim {d} has no unit-stride band; cannot vectorize",
                context={"backend": "pallas", "reason": "no_unit_stride"},
            )
        vec_band_for_dim.append(max(cands))
    vec_bands = sorted(set(vec_band_for_dim))
    if grid_bands is not None:
        vec_bands = [b for b in vec_bands if nest.band_names[b] not in grid_bands]
    gbs = [b for b in range(nest.n_bands) if b not in vec_bands]
    for inst in nest.instances:
        for d in range(rank):
            for b in vec_bands:
                if inst.A[d][b] not in (-1, 0, 1):
                    raise LowerFailure(
                        "vector band with non-unit stride",
                        context={"backend": "pallas",
                                 "reason": "non_unit_vector_stride"},
                    )

    grid = tuple(nest.band_extents[b] for b in gbs) or (1,)
    vec_extents = {b: nest.band_extents[b] for b in vec_bands}

    acc_plans = plan.plans
    if not plan.signs_ok:
        raise LowerFailure(
            "mixed coefficient signs per band; not vectorizable",
            context={"backend": "pallas", "reason": "mixed_signs"},
        )
    # Accesses, not just nest bands, must be unit-stride along the
    # vector bands: the kernel reads/writes each access through a
    # contiguous ``pl.ds`` window, so a coefficient like the 4 in
    # ``S[4*i]`` would silently alias the wrong contiguous elements
    # (the jax emitter gathers these; pallas refuses -> the sweep
    # engine's ``pallas->jax`` rung picks them up structurally).
    for racc, wacc in acc_plans:
        for rows_const in list(racc) + [wacc]:
            for row, _const in rows_const:
                for b in vec_bands:
                    if row[b] not in (-1, 0, 1):
                        raise LowerFailure(
                            f"access coefficient {row[b]} on the vector band "
                            "is not unit-stride; a contiguous pallas window "
                            "cannot express it",
                            context={"backend": "pallas",
                                     "reason": "strided_access"},
                        )

    space_order = [s.name for s in pattern.spaces]
    out_name = stmt.write.space
    out_pos = space_order.index(out_name)
    shapes = {s.name: s.concrete_shape(env) for s in pattern.spaces}
    dtypes = {s.name: s.dtype for s in pattern.spaces}
    _vmem_preflight(pattern, shapes, dtypes)
    env_dict = dict(env)

    def kernel(*refs):
        in_refs = {nm: r for nm, r in zip(space_order, refs[:len(space_order)])}
        out_ref = refs[len(space_order)]
        gvals = [pl.program_id(i) for i in range(len(gbs))] if gbs else []

        def base_of(rows_const):
            """(base index at vector-band==0/origin, vector band per dim)."""
            base, vb = [], []
            for row, const in rows_const:
                off = const
                for gi, b in enumerate(gbs):
                    off = off + row[b] * gvals[gi]
                bsel, bstep = -1, 1
                for b in vec_bands:
                    if row[b] != 0:
                        bsel, bstep = b, row[b]
                if bsel >= 0 and bstep == -1:
                    # ascending-index window: [off - (e-1), off]
                    off = off - (vec_extents[bsel] - 1)
                base.append(off)
                vb.append(bsel)
            return base, vb

        for racc, wacc in acc_plans:
            wbase, wvb = base_of(wacc)
            vals = []
            for acc, rows in zip(stmt.reads, racc):
                base, vb = base_of(rows)
                idx = tuple(
                    pl.ds(b0, vec_extents[bsel] if bsel >= 0 else 1)
                    for b0, bsel in zip(base, vb)
                )
                v = in_refs[acc.space][idx]
                perm = _axis_perm(vb, wvb)
                if perm is not None:
                    v = jnp.transpose(v, perm)
                vals.append(v)
            res = stmt.combine(vals, env_dict)
            want = tuple(1 if b < 0 else vec_extents[b] for b in wvb)
            res = jnp.asarray(res).astype(out_ref.dtype)
            if res.shape != want:
                res = jnp.broadcast_to(res, want)
            widx = tuple(
                pl.ds(b0, vec_extents[bsel] if bsel >= 0 else 1)
                for b0, bsel in zip(wbase, wvb)
            )
            out_ref[widx] = res

    call = pl.pallas_call(
        kernel,
        grid=grid,
        out_shape=jax.ShapeDtypeStruct(shapes[out_name], dtypes[out_name]),
        input_output_aliases={out_pos: 0},
        interpret=(mode == "interpret"),
    )

    def step(arrays: dict[str, jnp.ndarray]) -> dict[str, jnp.ndarray]:
        arrays = dict(arrays)
        arrays[out_name] = call(*[arrays[nm] for nm in space_order])
        return arrays

    step.pallas_mode = mode
    return step


def lower_pallas_parametric(
    pattern: PatternSpec, schedule: Schedule, cap_env: Mapping[str, int],
    *, params: tuple[str, ...] = ("n",), chunk: "int | tuple" = _PARAM_CHUNK,
    pnest: ParamNest | None = None, assume_full: bool = False,
    mode: str | None = None,
) -> Callable:
    """Grid-mapped twin of the strided parametric jax emitter.

    Builds ``step(arrays, pvals) -> arrays`` with the working-set
    parameter(s) as traced operands, exactly like
    :func:`lower_jax_parametric`'s strided path — same window geometry
    (:func:`_window_chunks` / :class:`_WindowPlan` / :func:`_read_hulls`),
    same caller contract (:func:`param_strided_in_bounds` per env) —
    but the mixed-radix trip space becomes the pallas *grid*: serial
    loop bands outermost, window bands (outer -> inner) innermost, one
    N-D ``pl.ds`` window per grid step. The grid is sized at *capacity*
    trip counts; steps past a rung's runtime radix are masked off
    in-kernel (``pl.when``), so one pallas executable serves the whole
    ladder (1 compile miss per ladder).

    Strided regime only: nests that would need the masked gather
    fallback raise :class:`~repro.core.schedule.SymbolicLowerError`, and
    drivers specialize per size instead (pallas has no parametric
    gather emitter).

    Compiled (Mosaic) kernels load lane windows only at offsets that are
    provably multiples of the lane chunk, so in ``"compiled"`` mode the
    lane window of grid step ``k`` starts at exactly ``k * C`` (no
    min-start overlap). That needs ``assume_full`` plus one more caller
    contract: every rung's lane extent is a multiple of the lane chunk
    (``Driver`` checks it and specializes ladders that break it).
    """
    from .schedule import SymbolicLowerError
    from jax.experimental.pallas import tpu as pltpu

    if pattern.kernel is not None:
        raise SymbolicLowerError(
            f"pattern {pattern.name!r} has a custom kernel; the parametric "
            "path cannot share it (env is baked into the step)"
        )
    if pnest is None:
        pnest = schedule.lower_symbolic(pattern.domain, params)
    splan = param_strided_plan(pattern, pnest)
    if splan is None:
        raise SymbolicLowerError(
            f"pattern {pattern.name!r} under schedule {schedule.name!r} is "
            "not strided-eligible; the pallas parametric path has no gather "
            "fallback — specialize per size instead"
        )
    mode = _resolve_pallas_mode(mode)
    params = tuple(params)
    stmt = pattern.statement
    w = splan.window_band
    wins, Cs = _window_chunks(pnest, splan, cap_env, chunk)
    C = Cs[w]
    aligned = mode == "compiled"
    if aligned and not assume_full:
        raise SymbolicLowerError(
            "compiled pallas lane windows start aligned and never mask: "
            "the ladder must tile every rung with full chunks"
        )
    rest_env = {k: int(v) for k, v in cap_env.items() if k not in params}
    wp = _WindowPlan(pnest, splan, wins, Cs)
    outer_wins = wins[:-1]
    grouped = [
        (_read_hulls(stmt, racc), wacc, s_w)
        for racc, wacc, s_w in splan.plans
    ]

    cap_scope = {k: int(v) for k, v in cap_env.items()}
    cap_ext = [max(0, e.eval(cap_scope)) for e in pnest.band_extents]
    # Static grid over the *capacity* trip space, loop bands outermost
    # and window bands innermost — pallas iterates the last grid dim
    # fastest, so execution order matches the jax emitter's mixed-radix
    # fori_loop step for step (loop-band writes stay last-value-wins).
    grid_order = list(wp.loop) + list(wins)
    grid = tuple(
        max(1, (cap_ext[b] + Cs[b] - 1) // Cs[b]) if b in Cs
        else max(1, cap_ext[b])
        for b in grid_order
    ) or (1,)

    space_order = [s.name for s in pattern.spaces]
    out_name = stmt.write.space
    out_pos = space_order.index(out_name)
    shapes = {s.name: s.concrete_shape(cap_env) for s in pattern.spaces}
    dtypes = {s.name: s.dtype for s in pattern.spaces}
    _vmem_preflight(pattern, shapes, dtypes)

    def kernel(*refs):
        in_refs = {nm: r for nm, r in zip(space_order, refs)}
        pv_ref = refs[len(space_order)]
        out_ref = refs[len(space_order) + 1]
        scope = {p: pv_ref[i] for i, p in enumerate(params)}
        cenv = {**rest_env, **scope}
        ext = [jnp.maximum(_affine_traced(e, scope), 0)
               for e in pnest.band_extents]
        ext_w = ext[w]
        nw = {b: (ext[b] + (Cs[b] - 1)) // Cs[b] for b in wins}
        win_lo = {b: ext[b] - Cs[b] for b in wins}
        idx = {b: pl.program_id(i) for i, b in enumerate(grid_order)}
        # runtime liveness: the capacity grid over-covers small rungs
        conds = [idx[b] < ext[b] for b in wp.loop]
        conds += [idx[b] < nw[b] for b in wins]
        # loop-invariant traced offsets, computed once per grid step
        tr = [
            (
                [
                    (space,
                     [(b, cf, _affine_traced(kc, scope))
                      for b, cf, kc in hull_rows],
                     spans, members)
                    for space, hull_rows, spans, members in groups
                ],
                [(b, cf, _affine_traced(kc, scope)) for b, cf, kc in wacc],
                s_w,
            )
            for groups, wacc, s_w in grouped
        ]
        lane = (None if assume_full
                else jax.lax.broadcasted_iota(jnp.int32, (C,), 0))

        def instance(groups, wacc, ws, ob, valid):
            """One instance's window step at window starts ``ws``; lanes
            where ``valid`` is False (masked lane mode) keep the target
            ref's current contents."""
            wstarts, wsizes, wsel, waxes = wp.spec(wacc, ws, ob)
            fit = wp.align(waxes)
            vals: list = [None] * len(stmt.reads)
            for space, hull_rows, spans, members in groups:
                starts, sizes, sel, raxes = wp.spec(hull_rows, ws, ob)
                hsizes = [s + sp for s, sp in zip(sizes, spans)]
                hull = in_refs[space][tuple(
                    pl.ds(st, hs) for st, hs in zip(starts, hsizes)
                )]
                for ridx, offs in members:
                    sub = hull[tuple(
                        slice(o, o + s) for o, s in zip(offs, sizes)
                    )]
                    vals[ridx] = fit(jnp, sub[sel], raxes)
            res = stmt.combine(vals, cenv)
            lanes = tuple(
                wp.lane_extent(b) if b is not None else 1 for b in waxes
            )
            res = jnp.broadcast_to(
                jnp.asarray(res).astype(out_ref.dtype), lanes)
            widx = tuple(pl.ds(st, sz) for st, sz in zip(wstarts, wsizes))
            if valid is None and all(cf == 1 for b, cf, _ in wacc if b >= 0):
                out_ref[widx] = res
                return
            # strided / reversed / masked write: blend into the window
            win = out_ref[widx]
            if valid is not None:
                vshape = tuple(C if b == w else 1 for b in waxes)
                res = jnp.where(valid.reshape(vshape), res, win[wsel])
            if all(s.step in (None, 1, -1) for s in wsel):
                # gap-free selector: the set IS the (possibly reversed)
                # value — .at[] with all-unit slices would make jnp build
                # an empty scatter-index constant, which a pallas kernel
                # cannot capture
                out_ref[widx] = res[wsel]
            else:
                out_ref[widx] = win.at[wsel].set(res)

        def body():
            ob = {b: idx[b] for b in wp.loop}
            # outer window bands always take full windows (chunks are
            # clamped to the ladder's smallest rung): min-start overlap
            ws0 = {b: jnp.minimum(idx[b] * Cs[b], win_lo[b])
                   for b in outer_wins}
            wsq = idx[w] * C
            for groups, wacc, s_w in tr:
                if assume_full:
                    ws = dict(ws0)
                    ws[w] = (pl.multiple_of(wsq, C) if aligned
                             else jnp.minimum(wsq, win_lo[w]))
                    instance(groups, wacc, ws, ob, None)
                    continue
                # sign-aware lane anchor, identical to the jax emitter
                wsl = jnp.minimum(wsq, win_lo[w])
                if s_w > 0:
                    wsl = jnp.maximum(wsl, 0)
                band = wsl + lane
                valid = (band >= 0) & (band < ext_w)
                ws = dict(ws0)
                ws[w] = wsl
                instance(groups, wacc, ws, ob, valid)

        if conds:
            live = conds[0]
            for c in conds[1:]:
                live = live & c
            pl.when(live)(body)
        else:
            body()

    # the traced parameters are scalars: they live in SMEM (Mosaic loads
    # no scalar from VMEM); the arrays stay whole in VMEM
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[vmem] * len(space_order)
        + [pl.BlockSpec(memory_space=pltpu.SMEM)],
        out_specs=vmem,
        out_shape=jax.ShapeDtypeStruct(shapes[out_name], dtypes[out_name]),
        input_output_aliases={out_pos: 0},
        interpret=(mode == "interpret"),
    )

    def step(arrays: dict[str, jnp.ndarray], pvals) -> dict[str, jnp.ndarray]:
        arrays = dict(arrays)
        pv = jnp.stack([jnp.asarray(v, jnp.int32) for v in pvals])
        arrays[out_name] = call(*[arrays[nm] for nm in space_order], pv)
        return arrays

    step.param_path = "strided"
    step.param_window_rank = len(wins)
    step.pallas_mode = mode
    return step
