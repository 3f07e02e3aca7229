"""Suite-layer + parametric-lowering tests (the PR-2/PR-4 contracts).

Covers: symbolic lowering equivalence with concrete lowering, the
parametric executable's value correctness against the serial oracle,
the one-compile-per-ladder cache property, the registry-wide lowering-
regime conformance sweep (every declarative workload record-equivalent
under parametric=False/"auto"/True, with the regime each one selects —
``extra.param_path`` — pinned by an explicit policy table), the
param_path override lever, registry round-trip against the harness
executor, the ladder/CSV re-export shim, the Spatter pattern specs, and
the disk-cache keying of ``TranslationCache.stats()``.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

import numpy as np
import pytest

from repro.core import (
    Affine,
    Driver,
    DriverConfig,
    SymbolicLowerError,
    TranslationCache,
    domain,
    gather,
    gather_scatter,
    identity,
    jacobi1d,
    scatter,
    triad,
)
from repro import suite
from repro.suite import collect_records, load_builtins

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))  # make the benchmarks package importable


# ---------------------------------------------------------------------------
# symbolic lowering
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("sch", [
    identity(),
    identity().tile_by_count("i", 4, outer="prog", inner="i"),
    identity().interleave("i", 2),
    identity().reverse("i"),
    identity().tile("i", 8),
    identity().tile_by_count("i", 4).interchange("i_T", "i_t"),
])
def test_symbolic_lowering_concretizes_to_concrete(sch):
    dom = domain(("i", 1, Affine.of("n") - 1))
    pnest = sch.lower_symbolic(dom, ("n",))
    for n in (10, 18, 66, 130):
        env = {"n": n}
        if not pnest.admits(env):
            continue
        assert pnest.concretize(env) == sch.lower(dom, env), (sch.name, n)


def test_symbolic_lowering_records_divisibility_constraints():
    dom = domain(("i", 0, "n"))
    pnest = identity().tile_by_count("i", 4).lower_symbolic(dom, ("n",))
    assert pnest.constraints == ((Affine.of("n"), 4),)
    assert pnest.admits({"n": 128}) and not pnest.admits({"n": 130})


def test_symbolic_lowering_rejects_triangular_domains():
    dom = domain(("i", 0, "n"), ("j", 0, "i"))
    with pytest.raises(SymbolicLowerError):
        identity().lower_symbolic(dom, ("n",))


def test_tile_by_count_matches_old_unified_tile():
    """The unified template's new split must generate the same nest as
    the old tile(extent // programs) form."""
    dom = domain(("i", 0, "n"))
    env = {"n": 64}
    new = identity().tile_by_count("i", 4, outer="prog", inner="i")
    old = identity().tile("i", 16, outer="prog", inner="i")
    assert (list(new.lower(dom, env).executed_points())
            == list(old.lower(dom, env).executed_points()))


# ---------------------------------------------------------------------------
# parametric pipeline: values + cache economics
# ---------------------------------------------------------------------------


def test_parametric_values_match_oracle_across_templates():
    for tmpl, factory, ns in [
        ("unified", triad, [256, 512, 1024]),
        ("independent", triad, [256, 512]),
        ("unified", jacobi1d, [258, 514]),
    ]:
        d = Driver(
            lambda env, f=factory: f(),
            DriverConfig(template=tmpl, programs=4, ntimes=2, reps=1,
                         parametric="auto"),
            cache=TranslationCache(),
        )
        d.validate_parametric(ns)


def test_parametric_ladder_compiles_exactly_once():
    """A 4-point ladder produces exactly 1 compile (and lower) miss on
    the parametric path — the whole ladder shares one executable."""
    cache = TranslationCache()
    d = Driver(lambda env: triad(),
               DriverConfig(template="unified", programs=4, ntimes=2,
                            reps=1, parametric="auto"), cache=cache)
    recs = d.run([256, 512, 1024, 2048])
    s = cache.stats()
    assert s["compile_misses"] == 1 and s["lower_misses"] == 1
    assert s["compile_hits"] == 3 and s["lower_hits"] == 3
    assert all(r.extra["parametric"] for r in recs)
    assert {r.extra["capacity"] for r in recs} == {2048}
    assert [r.n for r in recs] == [256, 512, 1024, 2048]


def test_parametric_falls_back_when_constraints_fail():
    """auto mode: a ladder whose points violate a symbolic divisibility
    assumption (here tile(48) with 48 ∤ n — the concrete path handles it
    with guards) silently specializes instead of sharing an executable."""
    cache = TranslationCache()
    d = Driver(lambda env: triad(),
               DriverConfig(template="independent", programs=2, ntimes=2,
                            reps=1, schedule=identity().tile("i", 48),
                            parametric="auto"), cache=cache)
    recs = d.run([256, 128])
    assert not any(r.extra["parametric"] for r in recs)
    assert cache.stats()["compile_misses"] == 2


def test_parametric_true_raises_when_unsupported():
    d = Driver(lambda env: triad(),
               DriverConfig(template="unified", programs=4, ntimes=2,
                            reps=1, backend="pallas", parametric=True),
               cache=TranslationCache())
    with pytest.raises(SymbolicLowerError):
        d.run([256])


# ---------------------------------------------------------------------------
# registered workloads: regime conformance (False / "auto" / True)
# ---------------------------------------------------------------------------

_IDENTITY_FIELDS = ("pattern", "template", "schedule", "backend", "n",
                    "working_set_bytes", "programs", "ntimes", "level")

# Which lowering regime every (workload, variant) is expected to select
# under parametric="auto" in quick mode. This is the auto policy's
# contract: unified programs>1 splits the outer band (multi-band nest ->
# gather), the independent template is single-band (-> strided), custom
# kernels and single-env-point groups cannot share an executable at all
# (-> specialized). A regression in the policy shows up here by name.
_EXPECTED_PATHS = {
    "fig05_barriers": {"barrier": "gather", "nowait": "gather"},
    "fig06_dataspaces": {"unified": "gather", "independent": "strided"},
    "fig07_streams": {None: "specialized"},        # single-point ladder
    "fig09_interleave": {None: "strided"},         # independent + interleave
    "fig10_counters": {None: "specialized"},       # single-point ladder
    "fig12_jacobi1d": {"unified": "gather", "independent": "strided",
                       "indep_padded": "strided"},
    "fig14_jacobi2d": {"unified": "gather", "independent": "strided"},
    "fig15_jacobi3d": {"unified": "gather", "independent": "strided"},
    "spatter_uniform": {None: "gather"},           # unified programs=4
    "mess_load_sweep": {None: "specialized"},      # one env point per group
    "pointer_chase": {None: "specialized"},        # custom kernel
    "spatter_nonuniform": {None: "gather"},        # unified programs=4
    "mess_calibrated": {None: "specialized"},      # zip: one env point/group
    "mess_contended": {None: "specialized"},       # mix kernel
    "device_sweep": {None: "strided"},             # independent template
    "derived_attention_kv": {None: "strided"},     # independent template
    "derived_moe_dispatch": {None: "specialized"},  # custom kernel
    "derived_lm_embed": {None: "specialized"},     # custom kernel
    "derived_train_update": {None: "strided"},     # independent template
    "derived_mla_decode": {None: "specialized"},   # custom kernel
    "spatter_ms1": {"ms1": "specialized",          # bound-index kernel
                    "uniform": "gather"},          # affine trace, programs=4
}

# parametric=True must raise for these (custom kernel with no
# variant-level parametric pin)
_TRUE_RAISES = {"pointer_chase", "derived_moe_dispatch", "derived_lm_embed",
                "spatter_ms1", "mess_contended", "derived_mla_decode"}

# Window dimensionality the strided regime must resolve per (workload,
# variant): 1-D nests window the lane band alone; the stencil nests
# window an (i x j[, k]) box per step (extra.param_window_rank). Only
# strided-regime variants appear here.
_EXPECTED_WINDOW_RANK = {
    ("fig06_dataspaces", "independent"): 1,
    ("fig09_interleave", None): 1,
    ("fig12_jacobi1d", "independent"): 1,
    ("fig12_jacobi1d", "indep_padded"): 1,
    ("fig14_jacobi2d", "independent"): 2,
    ("fig15_jacobi3d", "independent"): 3,
    ("device_sweep", None): 1,
    ("derived_attention_kv", None): 1,
    ("derived_train_update", None): 1,
}


def _shrunk(w):
    """Same workload with a cheap measurement budget (records stay
    comparable across modes because both use the same configs)."""
    variants = tuple(
        dataclasses.replace(
            v, config=dataclasses.replace(
                v.config, ntimes=min(v.config.ntimes, 4), reps=1))
        for v in w.variant_list(True)
    )
    return dataclasses.replace(w, variants=variants, post=None)


def _variant_of(label: str) -> str:
    return label.split("/")[1]


@pytest.mark.slow
def test_registry_conformance_across_lowering_regimes():
    """Every cataloged workload must produce record-equivalent results
    (same CSV labels, same identity fields) under parametric=False,
    "auto", and True — and auto must select exactly the regime the
    policy table above promises, reported via extra.param_path."""
    load_builtins()
    declarative = [w for w in suite.workloads() if w.runner is None]
    assert len(declarative) >= 10
    assert {w.name for w in declarative} == set(_EXPECTED_PATHS)
    for w in declarative:
        ws = _shrunk(w)
        # one shared cache: the specialized executables the False pass
        # builds are exactly what auto's fallback groups re-use
        cache = TranslationCache()
        spec = collect_records(ws, quick=True, cache=cache,
                               parametric=False)
        auto = collect_records(ws, quick=True, cache=cache,
                               parametric="auto")
        assert [lbl for lbl, _ in spec] == [lbl for lbl, _ in auto], w.name
        for (lbl, rs), (_, rp) in zip(spec, auto):
            for f in _IDENTITY_FIELDS:
                assert getattr(rs, f) == getattr(rp, f), (w.name, lbl, f)
            assert rs.extra["param_path"] == "specialized", (w.name, lbl)
        expect = _EXPECTED_PATHS[w.name]
        for lbl, rp in auto:
            want = expect.get(_variant_of(lbl), expect.get(None))
            assert rp.extra["param_path"] == want, (w.name, lbl)
            if want == "strided":
                rank = _EXPECTED_WINDOW_RANK.get(
                    (w.name, _variant_of(lbl)),
                    _EXPECTED_WINDOW_RANK.get((w.name, None)))
                assert rp.extra["param_window_rank"] == rank, (w.name, lbl)
        if w.name in _TRUE_RAISES:
            with pytest.raises(SymbolicLowerError):
                collect_records(ws, quick=True, cache=cache,
                                parametric=True)
            continue
        true = collect_records(ws, quick=True, cache=cache,
                               parametric=True)
        assert [lbl for lbl, _ in spec] == [lbl for lbl, _ in true], w.name
        for (lbl, rs), (_, rt) in zip(spec, true):
            for f in _IDENTITY_FIELDS:
                assert getattr(rs, f) == getattr(rt, f), (w.name, lbl, f)
            # True forces sharing wherever a variant leaves the policy
            # unset — including single-point groups
            if rt.extra["parametric"]:
                assert rt.extra["param_path"] in ("strided", "gather")


def test_workloads_share_single_executables_per_regime():
    load_builtins()
    # unified programs=4: the whole ladder shares one GATHER executable
    w = _shrunk(suite.workload("fig05_barriers"))
    cache = TranslationCache()
    recs = collect_records(w, quick=True, cache=cache, parametric="auto")
    n_points = len(w.ladder.points(True))
    assert n_points >= 4
    for label, rec in recs:
        assert rec.extra["parametric"], label
        assert rec.extra["param_path"] == "gather", label
    # one compile per (variant), not per (variant, point)
    assert cache.stats()["compile_misses"] == len(w.variant_list(True))
    # the independent template shares one STRIDED executable: 1 compile
    # miss for its whole ladder
    w6 = _shrunk(suite.workload("fig06_dataspaces"))
    indep = dataclasses.replace(
        w6, variants=tuple(v for v in w6.variant_list(True)
                           if v.label == "independent"))
    cache6 = TranslationCache()
    recs6 = collect_records(indep, quick=True, cache=cache6,
                            parametric="auto")
    assert [r.extra["param_path"] for _, r in recs6] \
        == ["strided"] * n_points
    assert cache6.stats()["compile_misses"] == 1


def test_stencil_ladders_run_nd_windows():
    """fig14/fig15 independent ladders — the paper's headline stencils —
    share one strided executable with multi-dimensional windows, and
    every record names the window rank."""
    load_builtins()
    for name, want_rank in (("fig14_jacobi2d", 2), ("fig15_jacobi3d", 3)):
        w = _shrunk(suite.workload(name))
        indep = dataclasses.replace(
            w, variants=tuple(v for v in w.variant_list(True)
                              if v.label == "independent"))
        cache = TranslationCache()
        recs = collect_records(indep, quick=True, cache=cache,
                               parametric="auto")
        assert [r.extra["param_path"] for _, r in recs] \
            == ["strided"] * len(recs), name
        assert [r.extra["param_window_rank"] for _, r in recs] \
            == [want_rank] * len(recs), name
        assert cache.stats()["compile_misses"] == 1, name


def test_param_path_override_pins_the_regime():
    """collect_records(param_path=...) pins the regime on auto configs —
    the conformance lever: forcing fig06's independent ladder onto
    gather must reproduce the strided records' identity fields."""
    load_builtins()
    w6 = _shrunk(suite.workload("fig06_dataspaces"))
    indep = dataclasses.replace(
        w6, variants=tuple(v for v in w6.variant_list(True)
                           if v.label == "independent"))
    cache = TranslationCache()
    strided = collect_records(indep, quick=True, cache=cache,
                              parametric="auto", param_path="strided")
    gathered = collect_records(indep, quick=True, cache=cache,
                               parametric="auto", param_path="gather")
    assert [r.extra["param_path"] for _, r in strided] \
        == ["strided"] * len(strided)
    assert [r.extra["param_path"] for _, r in gathered] \
        == ["gather"] * len(gathered)
    for (lbl, rs), (_, rg) in zip(strided, gathered):
        for f in _IDENTITY_FIELDS:
            assert getattr(rs, f) == getattr(rg, f), (lbl, f)


# ---------------------------------------------------------------------------
# backend axis: pallas records must mirror jax records (and the oracle)
# ---------------------------------------------------------------------------

_BACKEND_IDENTITY = tuple(f for f in _IDENTITY_FIELDS if f != "backend")

# Fast lane keeps the sweep cheap: the rank-1 stream workloads plus the
# 2-D stencil cover every pallas regime (strided parametric, specialized
# fallback for gather-only groups, single-point specialization). The 3-D
# stencil rides the slow lane.
_BACKEND_SWEEP_FAST = ("fig06_dataspaces", "fig07_streams",
                       "fig09_interleave", "fig14_jacobi2d")
_BACKEND_SWEEP_SLOW = ("fig15_jacobi3d",)


def _retargeted(w, backend):
    """The VariantSpec.backend override ``benchmarks.run --backend``
    applies, exercised through the library surface."""
    return dataclasses.replace(w, variants=tuple(
        dataclasses.replace(v, backend=backend)
        for v in w.variant_list(True)))


def _assert_backend_conformance(name):
    load_builtins()
    w = _shrunk(suite.workload(name))
    cache = TranslationCache()
    jax_recs = collect_records(w, quick=True, cache=cache, parametric="auto")
    pal_recs = collect_records(_retargeted(w, "pallas"), quick=True,
                               cache=cache, parametric="auto")
    # oracle agreement is enforced inside collect_records (workload
    # validation runs per group on the lowered step); here we pin the
    # record-level contract between the backends
    assert [lbl for lbl, _ in jax_recs] == [lbl for lbl, _ in pal_recs], name
    for (lbl, rj), (_, rp) in zip(jax_recs, pal_recs):
        assert rj.backend == "jax" and rp.backend == "pallas", (name, lbl)
        for f in _BACKEND_IDENTITY:
            assert getattr(rj, f) == getattr(rp, f), (name, lbl, f)
        assert rp.extra["pallas_mode"] in ("compiled", "interpret"), lbl
        assert rp.extra["donated"] is True, (name, lbl)
        # regime policy on the pallas backend: strided groups share the
        # grid-mapped executable at the same window rank; gather-only
        # groups specialize (pallas has no parametric gather emitter)
        pj, pp = rj.extra["param_path"], rp.extra["param_path"]
        if pj == "strided":
            assert pp == "strided", (name, lbl)
            assert rp.extra["param_window_rank"] \
                == rj.extra["param_window_rank"], (name, lbl)
        else:
            assert pp == "specialized", (name, lbl, pj, pp)


@pytest.mark.parametrize("name", _BACKEND_SWEEP_FAST)
def test_backend_conformance_fast(name):
    _assert_backend_conformance(name)


@pytest.mark.slow
@pytest.mark.parametrize("name", _BACKEND_SWEEP_SLOW)
def test_backend_conformance_slow(name):
    _assert_backend_conformance(name)


# ---------------------------------------------------------------------------
# registry round-trip + shims
# ---------------------------------------------------------------------------


def test_registry_round_trip_with_harness_executor():
    from benchmarks.run import registered_names

    names = registered_names()
    assert names == list(suite.names())
    for expected in ("fig05_barriers", "fig06_dataspaces", "fig07_streams",
                     "fig09_interleave", "fig10_counters", "fig12_jacobi1d",
                     "fig14_jacobi2d", "fig15_jacobi3d", "spatter_uniform",
                     "mess_load_sweep", "pointer_chase", "spatter_nonuniform",
                     "mess_calibrated", "fig16_tile_sweep", "roofline"):
        assert expected in names
    # lookups resolve and are well-formed (declarative entries carry a
    # sweep plan — a multi-axis one or a ladder's one-axis equivalent)
    for name in names:
        w = suite.workload(name)
        assert w.name == name
        if w.runner is None:
            assert w.sweep_plan().points(True)
        else:
            assert w.ladder is None and w.plan is None


def test_common_shim_reexports_suite_ladders():
    from benchmarks import common
    from repro.suite import FULL_SETS, QUICK_GRID, QUICK_SETS, WORKING_SETS

    assert common.QUICK_SETS == QUICK_SETS
    assert common.sets(True) == QUICK_SETS and common.sets(False) == FULL_SETS
    assert common.grids(True) == QUICK_GRID
    assert tuple(QUICK_SETS) == WORKING_SETS.quick


# ---------------------------------------------------------------------------
# Spatter patterns + disk-cache stats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("factory", [gather, scatter, gather_scatter])
@pytest.mark.parametrize("template", ["unified", "independent"])
def test_spatter_patterns_validate(factory, template):
    d = Driver(lambda env: factory(stride=4),
               DriverConfig(template=template, programs=4, ntimes=2,
                            reps=1), cache=TranslationCache())
    d.validate()


def test_spatter_accounting():
    pat = gather(stride=8)
    assert pat.bytes_per_point() == 8  # one read + one write, f32
    shapes = {s.name: s.concrete_shape({"n": 64}) for s in pat.spaces}
    assert shapes == {"D": (64,), "S": (512,)}


def test_stats_report_disk_cache_counters():
    s = TranslationCache().stats()
    assert set(s["disk"]) == {"enabled", "hits", "misses"}
    assert s["disk"]["hits"] >= 0 and s["disk"]["misses"] >= 0
