"""Pattern specifications — the analogue of the paper's header + ISCC files.

A :class:`PatternSpec` bundles exactly what AdaptMemBench's pattern
specification bundles:

    header (<kernel>.h)       -> DataSpace (allocation) + Access (memory
                                 mapping) + Statement (statement macro)
    <kernel>_init.in          -> DataSpace.init (init schedule is the
                                 identity scan of each space)
    <kernel>_run.in           -> PatternSpec.domain + a Schedule chosen at
                                 driver build time
    <kernel>_val.in           -> drivers.validate() replays the run
                                 schedule serially (numpy oracle) and
                                 compares

Statements are structured (reads/write/combine) rather than free-form C so
that one spec lowers to *both* backends (vectorized JAX and Pallas) and so
bandwidth accounting (bytes per point) is derived, not hand-entered.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping, Sequence

import numpy as np

from .domain import Affine, IterDomain, domain

__all__ = [
    "DataSpace",
    "Access",
    "Statement",
    "PatternSpec",
    "mix_patterns",
    "mix_space",
    "triad",
    "stream_copy",
    "stream_scale",
    "stream_sum",
    "nstream",
    "jacobi1d",
    "jacobi2d",
    "jacobi3d",
    "gather",
    "scatter",
    "gather_scatter",
    "pointer_chase",
]


@dataclasses.dataclass(frozen=True)
class DataSpace:
    """One allocated array. ``shape`` entries are params or ints (affine ok).

    ``init`` is a scalar, an :class:`Affine` in the params (a constant
    fixed by the env, such as a decode position ``n - 1``), or
    ``f(*index_grids)``."""

    name: str
    shape: tuple[Affine | int | str, ...]
    dtype: str = "float32"
    init: float | Affine | Callable[..., np.ndarray] = 0.0

    def concrete_shape(self, env: Mapping[str, int]) -> tuple[int, ...]:
        return tuple(Affine.of(s).eval(env) for s in self.shape)


@dataclasses.dataclass(frozen=True)
class Access:
    """space[index...] where each index is affine in domain iterators."""

    space: str
    index: tuple[Affine | int | str, ...]

    def resolved(self) -> tuple[Affine, ...]:
        return tuple(Affine.of(ix) for ix in self.index)


@dataclasses.dataclass(frozen=True)
class Statement:
    """``write = combine(*reads)`` executed at every domain point.

    ``combine`` receives one jnp/np array per read (already gathered for
    the current set of points) plus the param env as a keyword-free dict
    argument, and must be built from jax.numpy ops so it traces on both
    backends.
    """

    reads: tuple[Access, ...]
    write: Access
    combine: Callable[..., "np.ndarray"]  # combine(vals: list, env: dict)


@dataclasses.dataclass(frozen=True)
class PatternSpec:
    name: str
    spaces: tuple[DataSpace, ...]
    statement: Statement
    domain: IterDomain
    # flops executed per iteration point (for arithmetic-intensity reports)
    flops_per_point: int = 1
    # Serial-dependent patterns (pointer chase) cannot be expressed as an
    # affine statement over a data-parallel domain. ``kernel(pattern,
    # env) -> step(arrays) -> arrays`` replaces the generated jax step
    # wholesale (the schedule must stay the identity — drivers enforce
    # it), and ``oracle(pattern, arrays, env, ntimes) -> arrays`` is its
    # numpy ground truth for the validation stage. The affine
    # ``statement`` remains the accounting source (bytes per point).
    kernel: Callable | None = None
    oracle: Callable | None = None
    # Provenance of application-derived patterns (``repro.suite.derived``):
    # ``{source_model, source_op, feature_vector}``. Drivers merge it into
    # every record's ``extra["derived"]`` so hand-written and
    # application-derived records classify across origins.
    derived: Mapping[str, object] | None = None
    # Provenance of trace-driven patterns (``repro.suite.spatter_io``):
    # ``{source, pattern_hash, form}``. Drivers merge it into every
    # record's ``extra["trace"]`` so replayed traces stay attributable to
    # the JSON file (and pattern) they came from.
    trace: Mapping[str, object] | None = None
    # Multi-pattern mix accounting (``mix_patterns``): ``{primary,
    # components: ({label, pattern, points, bytes, fraction}, ...)}``
    # where ``bytes`` is per sweep. When set, drivers total the
    # components' bytes (the statement accounts the primary only) and
    # stamp the split into ``extra["mix"]``.
    mix: Mapping[str, object] | None = None
    # Custom kernels only. ``writes`` names the spaces the kernel
    # writes: the executable donates and returns those alone, and takes
    # the rest (weights, positions) as read-only operands. None: every
    # space is donated and threaded, as for the affine patterns.
    writes: tuple[str, ...] | None = None
    # ``traffic(env) -> bytes``: the compulsory bytes of one sweep where
    # no bytes-per-point count applies (a contraction over a cache);
    # None: the statement's bytes per point times the domain's points.
    traffic: Callable[[Mapping[str, int]], int] | None = None
    # ``(rtol, atol)`` of the oracle check in ``Driver.validate``: exact
    # up to float32 rounding by default; a pattern that stores bfloat16
    # states the rounding it is held to.
    tolerance: tuple[float, float] = (1e-5, 1e-5)

    def space(self, name: str) -> DataSpace:
        for s in self.spaces:
            if s.name == name:
                return s
        raise KeyError(name)

    # -- accounting (drivers use these for GB/s) ---------------------------

    def bytes_per_point(self) -> int:
        import numpy as _np

        total = 0
        for acc in (*self.statement.reads, self.statement.write):
            total += _np.dtype(self.space(acc.space).dtype).itemsize
        return total

    def sweep_bytes(self, env: Mapping[str, int]) -> int:
        """Compulsory bytes one sweep moves at ``env``: ``traffic`` where
        the pattern gives it, else bytes per point times points."""
        if self.traffic is not None:
            return int(self.traffic(env))
        return self.bytes_per_point() * self.domain.point_count(env)

    def allocate(self, env: Mapping[str, int]) -> dict[str, np.ndarray]:
        """Materialize + initialize all data spaces (the init schedule)."""
        out = {}
        for s in self.spaces:
            shape = s.concrete_shape(env)
            if isinstance(s.init, Affine):
                out[s.name] = np.full(shape, s.init.eval(env), dtype=s.dtype)
            elif callable(s.init):
                grids = np.meshgrid(
                    *[np.arange(n, dtype=np.int64) for n in shape], indexing="ij"
                ) if shape else []
                out[s.name] = np.asarray(s.init(*grids), dtype=s.dtype)
                if out[s.name].shape != shape:
                    out[s.name] = np.broadcast_to(out[s.name], shape).astype(s.dtype)
            else:
                out[s.name] = np.full(shape, s.init, dtype=s.dtype)
        return out


# ---------------------------------------------------------------------------
# Built-in pattern specs (the paper's case studies)
# ---------------------------------------------------------------------------


def triad(scalar: float = 3.0) -> PatternSpec:
    """STREAM triad: A[i] = B[i] + scalar * C[i]  (paper Listing 3/4)."""
    stmt = Statement(
        reads=(Access("B", ("i",)), Access("C", ("i",))),
        write=Access("A", ("i",)),
        combine=lambda vals, env: vals[0] + scalar * vals[1],
    )
    return PatternSpec(
        name="triad",
        spaces=(
            DataSpace("A", ("n",), "float32", 1.0),
            DataSpace("B", ("n",), "float32", 3.0),
            DataSpace("C", ("n",), "float32", 4.0),
        ),
        statement=stmt,
        domain=domain(("i", 0, "n")),
        flops_per_point=2,
    )


def stream_copy() -> PatternSpec:
    stmt = Statement(
        reads=(Access("B", ("i",)),),
        write=Access("A", ("i",)),
        combine=lambda vals, env: vals[0],
    )
    return PatternSpec(
        "copy",
        (DataSpace("A", ("n",), "float32", 0.0), DataSpace("B", ("n",), "float32", 2.0)),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=0,
    )


def stream_scale(scalar: float = 3.0) -> PatternSpec:
    stmt = Statement(
        reads=(Access("B", ("i",)),),
        write=Access("A", ("i",)),
        combine=lambda vals, env: scalar * vals[0],
    )
    return PatternSpec(
        "scale",
        (DataSpace("A", ("n",), "float32", 0.0), DataSpace("B", ("n",), "float32", 2.0)),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=1,
    )


def stream_sum() -> PatternSpec:
    stmt = Statement(
        reads=(Access("B", ("i",)), Access("C", ("i",))),
        write=Access("A", ("i",)),
        combine=lambda vals, env: vals[0] + vals[1],
    )
    return PatternSpec(
        "sum",
        (
            DataSpace("A", ("n",), "float32", 0.0),
            DataSpace("B", ("n",), "float32", 2.0),
            DataSpace("C", ("n",), "float32", 3.0),
        ),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=1,
    )


def nstream(k: int, scalar: float = 3.0) -> PatternSpec:
    """Paper Fig. 7: A[i] = sum of ``k`` read streams (k=2 reproduces sum,
    k=20 is the paper's maximum). One write stream + k read streams."""
    names = [f"S{j}" for j in range(k)]
    stmt = Statement(
        reads=tuple(Access(nm, ("i",)) for nm in names),
        write=Access("A", ("i",)),
        combine=lambda vals, env: sum(vals[1:], vals[0] * scalar),
    )
    spaces = (DataSpace("A", ("n",), "float32", 0.0),) + tuple(
        DataSpace(nm, ("n",), "float32", 1.0 + j) for j, nm in enumerate(names)
    )
    return PatternSpec(
        f"nstream{k}", spaces, stmt, domain(("i", 0, "n")), flops_per_point=k
    )


# -- Spatter-style gather/scatter (Lavin et al.) ----------------------------
#
# Spatter expresses memory benchmarks as pattern vectors driven through a
# fixed gather/scatter kernel; its UNIFORM:stride mode is affine, so the
# same specs drive this engine's schedule/template machinery. ``stride``
# plays Spatter's pattern-stride role: the sparse side touches one element
# per ``stride`` while the dense side streams contiguously.


def gather(stride: int = 8) -> PatternSpec:
    """Spatter gather: dense[i] = sparse[stride*i] (UNIFORM:stride)."""
    if stride < 1:
        raise ValueError("stride must be >= 1")
    i = Affine.of("i")
    stmt = Statement(
        reads=(Access("S", (i * stride,)),),
        write=Access("D", (i,)),
        combine=lambda vals, env: vals[0],
    )
    return PatternSpec(
        f"gather{stride}",
        (
            DataSpace("D", ("n",), "float32", 0.0),
            DataSpace("S", (Affine.of("n") * stride,), "float32",
                      lambda i: (i % 23).astype(np.float32)),
        ),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=0,
    )


def scatter(stride: int = 8) -> PatternSpec:
    """Spatter scatter: sparse[stride*i] = dense[i] (UNIFORM:stride)."""
    if stride < 1:
        raise ValueError("stride must be >= 1")
    i = Affine.of("i")
    stmt = Statement(
        reads=(Access("D", (i,)),),
        write=Access("S", (i * stride,)),
        combine=lambda vals, env: vals[0],
    )
    return PatternSpec(
        f"scatter{stride}",
        (
            DataSpace("D", ("n",), "float32",
                      lambda i: (i % 19).astype(np.float32)),
            DataSpace("S", (Affine.of("n") * stride,), "float32", 0.0),
        ),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=0,
    )


def gather_scatter(stride: int = 8) -> PatternSpec:
    """Spatter GS: sparse_out[stride*i] = sparse_in[stride*i] — both sides
    strided, the round-trip pattern Spatter uses to expose TLB/prefetch
    limits that one-sided gather or scatter alone hides."""
    if stride < 1:
        raise ValueError("stride must be >= 1")
    i = Affine.of("i")
    stmt = Statement(
        reads=(Access("S", (i * stride,)),),
        write=Access("T", (i * stride,)),
        combine=lambda vals, env: vals[0],
    )
    return PatternSpec(
        f"gather_scatter{stride}",
        (
            DataSpace("S", (Affine.of("n") * stride,), "float32",
                      lambda i: (i % 29).astype(np.float32)),
            DataSpace("T", (Affine.of("n") * stride,), "float32", 0.0),
        ),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=0,
    )


# -- concurrent multi-pattern mixes (the Mess contention primitive) ----------
#
# Mess (arXiv 2405.10170) argues that *contended* curves — a measured
# kernel sharing the memory system with generator traffic — predict
# application behavior where isolated kernels do not. ``mix_patterns``
# composes >= 2 PatternSpecs into ONE executable: each component keeps
# its own (namespaced) data spaces, every fused sweep runs every
# component's step, and the whole mix is timed as a unit, so the access
# streams contend for the same caches and memory channels for the full
# measurement. Per-component byte accounting rides ``PatternSpec.mix``
# into every record's ``extra["mix"]``.


def mix_space(k: int, name: str) -> str:
    """The namespaced array name of component ``k``'s space ``name``."""
    return f"m{k}_{name}"


def _concrete_component(spec: PatternSpec, env: Mapping[str, int]) -> PatternSpec:
    """Bake a component's symbolic shapes/bounds to ints under its own
    env, so components with *different* working sets coexist under the
    mix's single driver env."""
    from .domain import Dim

    try:
        spaces = tuple(
            dataclasses.replace(s, shape=s.concrete_shape(env))
            for s in spec.spaces
        )
        dims = tuple(
            Dim.of(d.name, d.lo.eval(env), d.hi.eval(env))
            for d in spec.domain.dims
        )
    except KeyError as e:
        raise ValueError(
            f"mix component {spec.name!r} is not rectangular under "
            f"{dict(env)!r} (unbound symbol {e}); mixes need "
            "parameter-bound rectangular domains"
        ) from None
    return dataclasses.replace(spec, spaces=spaces, domain=IterDomain(dims))


def _mix_kernel(components: tuple) -> Callable:
    def kernel(pattern, env):
        from .codegen import lower_mix

        return lower_mix(pattern, components)

    return kernel


def _mix_oracle(components: tuple) -> Callable:
    def oracle(pattern, arrays, env, ntimes):
        from .codegen import replay_component

        out = {k: np.array(v) for k, v in arrays.items()}
        for k, (_label, comp, cenv) in enumerate(components):
            sub = {s.name: out[mix_space(k, s.name)] for s in comp.spaces}
            sub = replay_component(comp, sub, cenv, int(ntimes))
            for s in comp.spaces:
                out[mix_space(k, s.name)] = np.asarray(sub[s.name])
        return out

    return oracle


def mix_patterns(
    components: Sequence[tuple],
    name: str = "mix",
    primary: str | None = None,
    trace: Mapping[str, object] | None = None,
) -> PatternSpec:
    """Compose patterns into one executable contending for memory.

    ``components`` is a sequence of ``(label, PatternSpec, env)`` tuples;
    each component is concretized under its *own* env (so a traffic
    generator can run a different working set than the measured kernel)
    and its spaces are renamed ``m{k}_<space>`` to keep the namespaces
    disjoint. The composed spec carries a custom kernel that runs every
    component's lowered step once per sweep (components alternate inside
    the fused ``ntimes`` loop — fine-grained temporal interleaving) and a
    numpy oracle replaying each component independently (disjoint spaces
    make the replay order immaterial).

    ``primary`` names the measured component (default: the first); its
    statement/domain provide the mix's nominal statement, and drivers
    report both the aggregate GB/s (all components' bytes over the
    shared wall time) and the per-component byte split
    (``extra["mix"]``) from which primary-bandwidth-under-load derives.
    Custom kernels must run ``template="unified"``/``programs=1``.
    """
    comps = tuple(
        (str(label), _concrete_component(spec, dict(env)), dict(env))
        for label, spec, env in components
    )
    if not comps:
        raise ValueError("mix_patterns needs at least one component")
    labels = [label for label, _, _ in comps]
    if len(set(labels)) != len(labels):
        raise ValueError(f"duplicate component labels: {labels}")
    primary = primary if primary is not None else labels[0]
    if primary not in labels:
        raise ValueError(f"primary {primary!r} not among {labels}")
    entries = []
    for label, comp, cenv in comps:
        pts = comp.domain.point_count(cenv)
        entries.append({
            "label": label,
            "pattern": comp.name,
            "points": int(pts),
            "bytes": int(comp.bytes_per_point() * pts),
        })
    total = sum(e["bytes"] for e in entries)
    for e in entries:
        e["fraction"] = (e["bytes"] / total) if total else 0.0
    pk = labels.index(primary)
    prim = comps[pk][1]
    spaces = tuple(
        dataclasses.replace(s, name=mix_space(k, s.name))
        for k, (_, comp, _) in enumerate(comps)
        for s in comp.spaces
    )
    stmt = Statement(
        reads=tuple(
            Access(mix_space(pk, a.space), a.index)
            for a in prim.statement.reads
        ),
        write=Access(mix_space(pk, prim.statement.write.space),
                     prim.statement.write.index),
        combine=prim.statement.combine,
    )
    return PatternSpec(
        name=name,
        spaces=spaces,
        statement=stmt,
        domain=prim.domain,
        flops_per_point=prim.flops_per_point,
        kernel=_mix_kernel(comps),
        oracle=_mix_oracle(comps),
        trace=trace,
        mix={"primary": primary, "components": tuple(entries)},
    )


# -- pointer chase (latency, not bandwidth) ----------------------------------
#
# The load-to-use latency probe every memory characterization needs (the
# lat_mem_rd lineage; Mess pairs exactly this with bandwidth under load):
# H = P[H] repeated n times per sweep, where P is a single-cycle
# pseudorandom permutation of [0, n). Every load's address depends on the
# previous load's *value*, so the chain cannot be overlapped or
# prefetched — per-step time is the load-to-use latency of whatever
# level the working set sits in. Serial dependence is inexpressible as
# an affine statement, so this spec carries a custom ``kernel``/
# ``oracle`` pair; the affine statement remains for accounting only.


def _chase_cycle(i: np.ndarray) -> np.ndarray:
    """Single-cycle pseudorandom permutation of [0, n): visit elements in
    a shuffled order and link each to the next. One cycle guarantees the
    chase touches the whole working set; the shuffle defeats stride
    prefetchers. Deterministic per size (seeded by n)."""
    n = int(i.shape[0])
    order = np.random.default_rng(0xC4A5E ^ n).permutation(n)
    p = np.empty(n, dtype=np.int32)
    p[order] = np.roll(order, -1).astype(np.int32)
    return p


def _chase_kernel(pattern: PatternSpec, env: Mapping[str, int]) -> Callable:
    """``step(arrays)``: chase ``n`` serially-dependent loads through P,
    parking the running index in the one-element head space H."""
    steps = int(env["n"])

    def step(arrays):
        import jax

        arrays = dict(arrays)
        P = arrays["P"]
        h = jax.lax.fori_loop(0, steps, lambda _, h: P[h], arrays["H"][0])
        arrays["H"] = arrays["H"].at[0].set(h)
        return arrays

    return step


def _chase_oracle(pattern: PatternSpec, arrays: Mapping[str, np.ndarray],
                  env: Mapping[str, int], ntimes: int) -> dict:
    out = {k: np.array(v) for k, v in arrays.items()}
    P = out["P"]
    h = int(out["H"][0])
    for _ in range(int(ntimes) * int(env["n"])):
        h = int(P[h])
    out["H"][0] = h
    return out


def pointer_chase() -> PatternSpec:
    """Serial pointer chase: H = P[H], n dependent loads per sweep.

    A latency pattern: the derived metric is seconds / (ntimes * n) —
    load-to-use ns per access — not GB/s (the statement's 8 bytes/point
    accounting is nominal). Use with ``template="unified"`` and
    ``programs=1``; the chain is inherently serial.
    """
    stmt = Statement(
        reads=(Access("P", ("i",)),),
        write=Access("H", (0,)),
        combine=lambda vals, env: vals[0],
    )
    return PatternSpec(
        "pointer_chase",
        (
            DataSpace("P", ("n",), "int32", _chase_cycle),
            DataSpace("H", (1,), "int32", 0),
        ),
        stmt,
        domain(("i", 0, "n")),
        flops_per_point=0,
        kernel=_chase_kernel,
        oracle=_chase_oracle,
    )


def jacobi1d() -> PatternSpec:
    """3-pt Jacobi 1D: A[i] = (B[i-1] + B[i] + B[i+1]) / 3 on 1 <= i < n-1."""
    third = np.float32(1.0 / 3.0)
    stmt = Statement(
        reads=(
            Access("B", (Affine.of("i") - 1,)),
            Access("B", ("i",)),
            Access("B", (Affine.of("i") + 1,)),
        ),
        write=Access("A", ("i",)),
        combine=lambda vals, env: (vals[0] + vals[1] + vals[2]) * third,
    )
    return PatternSpec(
        "jacobi1d",
        (
            DataSpace("A", ("n",), "float32", 0.0),
            DataSpace("B", ("n",), "float32", lambda i: (i % 17).astype(np.float32)),
        ),
        stmt,
        domain(("i", 1, Affine.of("n") - 1)),
        flops_per_point=3,
    )


def jacobi2d() -> PatternSpec:
    """5-pt star (the paper's '9-pt Jacobi 2D' figure uses the standard
    star/box family; we implement the 5-pt star and the 9-pt box — this
    constructor is the 5-pt star; see jacobi2d9 for the box)."""
    fifth = np.float32(1.0 / 5.0)
    i, j = Affine.of("i"), Affine.of("j")
    stmt = Statement(
        reads=(
            Access("B", (i - 1, j)),
            Access("B", (i + 1, j)),
            Access("B", (i, j - 1)),
            Access("B", (i, j + 1)),
            Access("B", (i, j)),
        ),
        write=Access("A", (i, j)),
        combine=lambda vals, env: (vals[0] + vals[1] + vals[2] + vals[3] + vals[4])
        * fifth,
    )
    return PatternSpec(
        "jacobi2d",
        (
            DataSpace("A", ("n", "n"), "float32", 0.0),
            DataSpace(
                "B",
                ("n", "n"),
                "float32",
                lambda i, j: ((i + 2 * j) % 13).astype(np.float32),
            ),
        ),
        stmt,
        domain(("i", 1, Affine.of("n") - 1), ("j", 1, Affine.of("n") - 1)),
        flops_per_point=5,
    )


def jacobi2d9() -> PatternSpec:
    """9-pt box Jacobi 2D (paper Fig. 13)."""
    ninth = np.float32(1.0 / 9.0)
    i, j = Affine.of("i"), Affine.of("j")
    reads = tuple(
        Access("B", (i + di, j + dj)) for di in (-1, 0, 1) for dj in (-1, 0, 1)
    )
    stmt = Statement(
        reads=reads,
        write=Access("A", (i, j)),
        combine=lambda vals, env: sum(vals[1:], vals[0]) * ninth,
    )
    return PatternSpec(
        "jacobi2d9",
        (
            DataSpace("A", ("n", "n"), "float32", 0.0),
            DataSpace(
                "B",
                ("n", "n"),
                "float32",
                lambda i, j: ((3 * i + j) % 11).astype(np.float32),
            ),
        ),
        stmt,
        domain(("i", 1, Affine.of("n") - 1), ("j", 1, Affine.of("n") - 1)),
        flops_per_point=9,
    )


def jacobi3d() -> PatternSpec:
    """7-pt Jacobi 3D (paper §III-B / Listing 9)."""
    seventh = np.float32(1.0 / 7.0)
    i, j, k = Affine.of("i"), Affine.of("j"), Affine.of("k")
    stmt = Statement(
        reads=(
            Access("B", (i - 1, j, k)),
            Access("B", (i + 1, j, k)),
            Access("B", (i, j - 1, k)),
            Access("B", (i, j + 1, k)),
            Access("B", (i, j, k - 1)),
            Access("B", (i, j, k + 1)),
            Access("B", (i, j, k)),
        ),
        write=Access("A", (i, j, k)),
        combine=lambda vals, env: sum(vals[1:], vals[0]) * seventh,
    )
    n1 = Affine.of("n") - 1
    return PatternSpec(
        "jacobi3d",
        (
            DataSpace("A", ("n", "n", "n"), "float32", 0.0),
            DataSpace(
                "B",
                ("n", "n", "n"),
                "float32",
                lambda i, j, k: ((i + j + k) % 7).astype(np.float32),
            ),
        ),
        stmt,
        domain(("i", 1, n1), ("j", 1, n1), ("k", 1, n1)),
        flops_per_point=7,
    )
