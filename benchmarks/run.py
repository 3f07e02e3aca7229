"""Benchmark harness entrypoint — a generic executor over the registry.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig05,fig16]
                                            [--tag spatter,mess]
                                            [--smoke] [--list]
                                            [--backend jax|pallas]
                                            [--jobs N]
                                            [--pattern-file CAPTURE.json]
                                            [--out BENCH.json]

Every experiment is a declarative ``repro.suite`` Workload (pattern x
schedule variants x sweep plan x validation policy) registered by name;
this module just iterates the registry and prints the paper's
machine-parsable ``name,us_per_call,derived`` CSV contract. ``--list``
prints the registered names (with tags), ``--only`` filters by name or
figure prefix, ``--tag`` filters by scenario-family tag (``paper-figs``,
``spatter``, ``mess``, ``latency``, ``trace``); both filters compose
(AND).

``--pattern-file CAPTURE.json`` registers a trace-replay workload for a
user-captured Spatter JSON pattern file (``repro.suite.spatter_io``) and
runs it with the batch: each pattern entry becomes a variant riding its
regime-appropriate config — affine traces on the strided paths,
value-dependent ones on the bound-index kernel regime — through the
same sweep engine as every built-in. A malformed file fails up front
with the parser's typed reason slug, not mid-sweep.

``--backend pallas`` re-targets every declarative workload at the pallas
backend (the ``VariantSpec.backend`` override — configs are rewritten,
not rebuilt). Workloads the pallas backend cannot express — custom
runners and custom-kernel patterns (pointer chase, nonuniform spatter)
— are *skipped* with a structured ``{workload, backend, reason}`` entry
in the ledger's ``skipped`` section instead of crashing; per-point
faults inside eligible workloads still walk the engine's demotion
ladder (``pallas->jax`` first).

``--jobs N`` (N > 1) runs each workload's plan through the plan
engine's :class:`~repro.suite.engine.ThreadPoolBackend` — independent
driver groups stage and measure concurrently (measurement serialized
per device, so timing fidelity is preserved) while the emitted records
stay identical to serial order. Custom-runner workloads own their
execution and ignore the flag.

``--smoke`` runs every selected workload in quick mode and writes a JSON
perf ledger (default ``BENCH_PR9.json`` at the repo root) with
per-workload wall time and per-phase (stage vs measure) split, an
``executor`` block ({backend, workers, staging_overlap_seconds, ...})
aggregated across workloads, the process-wide translation-cache hit rate,
capacity, and evictions (in-process lower/compile counters and the jax
disk compile cache), and two probes ``scripts/ci.sh`` gates on:

* ``param_path_probe`` — for strided-eligible ladders, the per-call
  cost of the strided-parametric regime against the specialized strided
  path (plus the 1-compile-per-ladder assertion), gating the
  regime-comparability floor (strided ≤ 1.5x specialized) that makes
  ``programs``-axis sweeps trustworthy.
* ``pallas_probe`` — the pallas backend against the jax backend on the
  same strided-parametric ladders (interleaved ``time_pair`` timing,
  1-compile-per-ladder on the pallas side, per-side
  ``timing_quality``), stamping the platform-resolved execution mode
  (``compiled`` where the platform lowers pallas natively,
  ``interpret`` elsewhere) so CI can gate a calibrated backend-overhead
  ceiling per mode.

The ledger also carries a ``derived`` block: for every
application-derived workload that ran (``repro.suite.derived`` — access
shapes mined from the compiled HLO of the repo's own models), the
source model, the mined source op, and the architecture-independent
feature vector (stride entropy, reuse distance, gather fraction), which
``scripts/ci.sh`` gates for presence and non-degeneracy. Two more gated
blocks cover the trace layer: ``trace`` (per trace workload, each
pattern's parsed provenance and a live bit-exact replay check against
the direct numpy replay of the JSON) and ``contended`` (the
multi-pattern mix study: per-pattern byte-split integrity and the
isolated-vs-contended primary-bandwidth ratio).

The harness is fault-isolated end to end: a failing workload (or a
failing plan *point* inside one — the engine demotes/retries and
reports per-point ``FailureRecord``s) never stops the batch. Every
failure lands in the ledger's ``failures`` section as a structured
``{workload, stage, error, point, message}`` entry and the run exits
nonzero with a summary. ``--journal DIR`` makes each workload's sweep
resumable (one JSONL journal per workload under DIR): re-invoking after
a kill replays completed points and measures only the remainder.
"""
from __future__ import annotations

import argparse
import importlib
import json
import pathlib
import sys
import time


# Modules that register *custom* (non-declarative) workloads on import;
# the declarative entries live in repro.suite.catalog.
CUSTOM_MODULES = [
    "fig16_tile_sweep",
    "roofline",
]

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _param_path_probe() -> dict:
    """Strided-parametric vs specialized per-call cost on catalog-shaped
    strided-eligible ladders (independent-template streams/stencils —
    the exact configurations fig06/fig09/fig12/fig14 and the mess
    variants run under the strided regime). Both sides are **donated**
    executables, so the comparison is copy-free on both sides.

    Wall-clock on this container is noisy (shared cores), so the probe
    is built to survive it: per rung, the two executables are timed via
    ``repro.core.measure.time_pair`` — *interleaved* A/B calls (both see
    the same load environment) — and the per-rung ratio uses
    min-of-reps (a load spike inflates a call, never deflates it). The
    gated number is the geometric mean across rungs; each probe entry
    also reports ``timing_quality`` (median/min/CV/reps per side, the
    same payload every sweep Record stamps). Also asserts the regime
    every record selected, the parametric run's compile misses (must be
    1: one executable per ladder), and the window rank
    (``jacobi2d_indep`` must report rank-2 N-D windows).
    """
    import dataclasses as _dc
    import math

    import jax.numpy as _jnp

    from repro.core import (
        Driver,
        DriverConfig,
        TranslationCache,
        identity,
        jacobi1d,
        jacobi2d,
        triad,
    )
    from repro.core.measure import TimingResult, time_pair

    stream_ladder = [1 << 14, 1 << 16, 1 << 17]
    # grid ladder: extents 128/256 are multiples of the min-rung chunk
    # (128), so the N-D windows tile each rung exactly (no overlap
    # slack); rungs stay a sizable fraction of the capacity pitch (the
    # strided side reads capacity-pitched rows, whose relative cost
    # grows as rungs shrink) and bursts are long (ntimes=32) so per-call
    # fixed overhead does not drown the per-point comparison
    grid_ladder = [130, 258]
    probes = {
        "triad_indep": (lambda env: triad(),
                        DriverConfig(template="independent", programs=4,
                                     ntimes=16), stream_ladder),
        "jacobi1d_indep": (lambda env: jacobi1d(),
                           DriverConfig(template="independent", programs=4,
                                        ntimes=16), stream_ladder),
        "triad_il2_indep": (lambda env: triad(),
                            DriverConfig(template="independent", programs=2,
                                         ntimes=16,
                                         schedule=identity().interleave(
                                             "i", 2)), stream_ladder),
        # the 2D stencil ladder: rank-2 dynamic windows (i-chunk x
        # j-chunk boxes) must stay regime-comparable too
        "jacobi2d_indep": (lambda env: jacobi2d(),
                           DriverConfig(template="independent", programs=4,
                                        ntimes=32), grid_ladder),
    }
    out = {}
    for name, (fac, cfg, ladder) in probes.items():
        spec_d = Driver(fac, _dc.replace(cfg, parametric=False),
                        cache=TranslationCache())
        pcache = TranslationCache()
        par_d = Driver(fac, _dc.replace(cfg, parametric=True,
                                        param_path="strided"), cache=pcache)
        spec_ps = spec_d.prepare(ladder)
        par_ps = par_d.prepare(ladder)
        compile_misses = pcache.stats()["compile_misses"]
        paths = sorted({
            (p.compiled.param_path if p.parametric else "specialized")
            for p in par_ps
        })
        ranks = sorted({
            (p.compiled.param_window_rank if p.parametric else 0)
            for p in par_ps
        })
        # temporally separated passes per rung, min across passes:
        # ambient load on this container drifts on second-scale
        # timescales, so a single unlucky window can inflate a whole
        # rung — each pass re-samples under (usually) different load,
        # and min is the honest matched-load estimator (spikes inflate,
        # never deflate). Each pass is one time_pair alternation block;
        # the samples accumulate so the reported CV covers every pass.
        # Sampling is *adaptive* (the same discipline `time_fn` applies
        # per record): at least 3 passes, and while the geomean ratio
        # sits near the CI gate floor (> 1.4) extra passes run until the
        # estimate stabilizes (< 2% movement) or the pass budget (6) is
        # spent — a gate decision should rest on a converged estimate,
        # not on however loud the container happened to be.
        samples_s: list[list[float]] = [[] for _ in ladder]
        samples_p: list[list[float]] = [[] for _ in ladder]

        def _one_pass() -> None:
            for i, (sp, pp) in enumerate(zip(spec_ps, par_ps)):
                s_tup = tuple(
                    _jnp.asarray(v) for _, v in sorted(
                        sp.lowered.pattern.allocate(
                            sp.lowered.env).items()))
                p_tup = tuple(
                    _jnp.asarray(v) for _, v in sorted(
                        pp.lowered.pattern.allocate(
                            pp.lowered.env).items()))
                ts, tp = time_pair(sp.executable(), (s_tup,),
                                   pp.executable(), (p_tup,), reps=7)
                samples_s[i].extend(ts.all_seconds)
                samples_p[i].extend(tp.all_seconds)

        def _geomean_ratio() -> float:
            rs = [min(p) / min(s) for s, p in zip(samples_s, samples_p)]
            return math.exp(sum(math.log(x) for x in rs) / len(rs))

        gm = float("inf")
        for _pass in range(6):
            _one_pass()
            prev, gm = gm, _geomean_ratio()
            if _pass >= 2 and (gm <= 1.4 or abs(gm - prev) < 0.02 * prev):
                break

        def _timing(samples: list[float]) -> TimingResult:
            ordered = sorted(samples)
            return TimingResult(ordered[len(ordered) // 2], len(samples),
                                tuple(samples))

        t_s = [_timing(s) for s in samples_s]
        t_p = [_timing(s) for s in samples_p]
        best_s = [t.minimum for t in t_s]
        best_p = [t.minimum for t in t_p]
        ratios = [tp / ts for ts, tp in zip(best_s, best_p)]
        out[name] = {
            "ns": ladder,
            "specialized_us": [round(t * 1e6, 2) for t in best_s],
            "strided_us": [round(t * 1e6, 2) for t in best_p],
            "per_point_ratio": [round(x, 3) for x in ratios],
            "ratio": round(
                math.exp(sum(math.log(x) for x in ratios) / len(ratios)), 3),
            "param_path": paths,
            "window_rank": ranks,
            "compile_misses": compile_misses,
            "timing_quality": {
                "specialized": [t.quality() for t in t_s],
                "strided": [t.quality() for t in t_p],
            },
        }
    return out


def _pallas_probe() -> dict:
    """Pallas-backend vs jax-backend per-call cost on the same
    strided-parametric ladders ``_param_path_probe`` gates (one rank-1
    stream, one rank-2 stencil). Both sides are donated one-executable-
    per-ladder parametric drivers; the only variable is the backend, so
    the geomean ratio IS the pallas lowering overhead on this platform.

    Timing discipline matches ``_param_path_probe``: interleaved
    ``time_pair`` alternation blocks (both sides see the same ambient
    load), min-of-reps per rung, adaptive pass count, per-side
    ``timing_quality``. The probe additionally asserts pallas-backend
    parity contracts: exactly 1 compile miss per ladder on the pallas
    cache, every record on the strided regime, and the platform-probed
    execution mode (``pallas_mode``) stamped for CI — ``compiled``
    platforms gate that mode, interpret-only platforms (CPU) gate a
    wider calibrated ratio ceiling instead.
    """
    import dataclasses as _dc
    import math

    import jax.numpy as _jnp

    from repro.core import Driver, DriverConfig, TranslationCache, jacobi2d, triad
    from repro.core.codegen import pallas_platform_mode
    from repro.core.measure import TimingResult, time_pair

    mode = pallas_platform_mode()
    stream_ladder = [1 << 14, 1 << 16]
    grid_ladder = [130, 258]
    probes = {
        "triad_indep": (lambda env: triad(),
                        DriverConfig(template="independent", programs=4,
                                     ntimes=16), stream_ladder),
        "jacobi2d_indep": (lambda env: jacobi2d(),
                           DriverConfig(template="independent", programs=4,
                                        ntimes=32), grid_ladder),
    }
    out: dict = {"pallas_mode": mode, "workloads": {}}
    for name, (fac, cfg, ladder) in probes.items():
        jax_d = Driver(fac, _dc.replace(cfg, parametric=True,
                                        param_path="strided"),
                       cache=TranslationCache())
        pcache = TranslationCache()
        pal_d = Driver(fac, _dc.replace(cfg, backend="pallas",
                                        parametric=True,
                                        param_path="strided"), cache=pcache)
        jax_ps = jax_d.prepare(ladder)
        pal_ps = pal_d.prepare(ladder)
        compile_misses = pcache.stats()["compile_misses"]
        paths = sorted({
            (p.compiled.param_path if p.parametric else "specialized")
            for p in pal_ps
        })
        modes = sorted({p.lowered.pallas_mode for p in pal_ps})
        samples_j: list[list[float]] = [[] for _ in ladder]
        samples_p: list[list[float]] = [[] for _ in ladder]

        def _one_pass() -> None:
            for i, (jp, pp) in enumerate(zip(jax_ps, pal_ps)):
                j_tup = tuple(
                    _jnp.asarray(v) for _, v in sorted(
                        jp.lowered.pattern.allocate(
                            jp.lowered.env).items()))
                p_tup = tuple(
                    _jnp.asarray(v) for _, v in sorted(
                        pp.lowered.pattern.allocate(
                            pp.lowered.env).items()))
                tj, tp = time_pair(jp.executable(), (j_tup,),
                                   pp.executable(), (p_tup,), reps=7)
                samples_j[i].extend(tj.all_seconds)
                samples_p[i].extend(tp.all_seconds)

        def _geomean_ratio() -> float:
            rs = [min(p) / min(j) for j, p in zip(samples_j, samples_p)]
            return math.exp(sum(math.log(x) for x in rs) / len(rs))

        gm = float("inf")
        for _pass in range(6):
            _one_pass()
            prev, gm = gm, _geomean_ratio()
            if _pass >= 2 and abs(gm - prev) < 0.02 * prev:
                break

        def _timing(samples: list[float]) -> TimingResult:
            ordered = sorted(samples)
            return TimingResult(ordered[len(ordered) // 2], len(samples),
                                tuple(samples))

        t_j = [_timing(s) for s in samples_j]
        t_p = [_timing(s) for s in samples_p]
        best_j = [t.minimum for t in t_j]
        best_p = [t.minimum for t in t_p]
        ratios = [tp / tj for tj, tp in zip(best_j, best_p)]
        out["workloads"][name] = {
            "ns": ladder,
            "jax_us": [round(t * 1e6, 2) for t in best_j],
            "pallas_us": [round(t * 1e6, 2) for t in best_p],
            "per_point_ratio": [round(x, 3) for x in ratios],
            "ratio": round(
                math.exp(sum(math.log(x) for x in ratios) / len(ratios)), 3),
            "param_path": paths,
            "pallas_mode": modes,
            "compile_misses": compile_misses,
            "timing_quality": {
                "jax": [t.quality() for t in t_j],
                "pallas": [t.quality() for t in t_p],
            },
        }
    return out


def _pallas_ineligible(w, quick: bool) -> str | None:
    """Workload-level pallas eligibility for the ``--backend pallas``
    rewrite. Custom-kernel patterns (arbitrary jax callables — pointer
    chase, nonuniform spatter) are the one structural property no
    demotion rung can lower around, so they skip up front with a
    structured reason; anything affine proceeds and lets the engine's
    per-point ``pallas->jax`` rung absorb residual refusals. A factory
    that fails to instantiate reports as ineligible too — it would fail
    identically inside the engine."""
    pts = w.sweep_plan().points(quick)
    for v in w.variant_list(quick):
        factory = v.pattern or w.pattern
        if factory is None:
            return "no_pattern_factory"
        seen: set = set()
        for pt in pts:
            if pt.pattern_kwargs in seen:
                continue
            seen.add(pt.pattern_kwargs)
            try:
                pat = factory(dict(pt.env), **dict(pt.pattern_kwargs)) \
                    if pt.pattern_kwargs else factory(dict(pt.env))
            except Exception as e:  # noqa: BLE001
                return f"factory_probe: {type(e).__name__}: {e}"
            if pat.kernel is not None:
                return "custom_kernel"
    return None


def load_registry() -> tuple[list[str], dict[str, str]]:
    """Load all workloads; a custom module that fails to import becomes a
    per-module failure entry instead of killing the whole harness."""
    from repro import suite

    suite.load_builtins()
    import_errors: dict[str, str] = {}
    for name in CUSTOM_MODULES:
        try:
            importlib.import_module(f"benchmarks.{name}")
        except Exception as e:  # noqa: BLE001
            import_errors[name] = f"{type(e).__name__}: {e}"
    return list(suite.names()), import_errors


def registered_names() -> list[str]:
    """All workload names, declarative builtins + custom modules."""
    return load_registry()[0]


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default="",
                    help="comma-separated workload names or figure prefixes")
    ap.add_argument("--tag", default="",
                    help="comma-separated scenario-family tags "
                         "(paper-figs, spatter, mess, latency, trace)")
    ap.add_argument("--list", action="store_true",
                    help="print registered workload names (+tags) and exit")
    ap.add_argument("--smoke", action="store_true",
                    help="quick mode + write a JSON perf ledger")
    ap.add_argument("--backend", default="", choices=("", "jax", "pallas"),
                    help="re-target declarative workloads at this backend "
                         "(VariantSpec.backend override); pallas-ineligible "
                         "workloads skip with a structured ledger entry")
    ap.add_argument("--jobs", type=int, default=1,
                    help="worker threads for the plan engine's execution "
                         "backend; >1 selects ThreadPoolBackend (records "
                         "stay identical to serial order)")
    ap.add_argument("--pattern-file", default="",
                    help="Spatter JSON pattern file to replay as a "
                         "trace workload alongside the selected batch")
    ap.add_argument("--out", default=str(ROOT / "BENCH_PR10.json"),
                    help="ledger path for --smoke")
    ap.add_argument("--journal", default="",
                    help="directory for per-workload resume journals; "
                         "re-invoking replays completed points")
    args = ap.parse_args(argv)

    # the cross-process leg of the translation cache: compile time is
    # reported separately, and re-runs skip the backend compiles
    from repro.core.staging import enable_persistent_cache

    enable_persistent_cache(str(ROOT / "experiments" / ".jax_cache"))
    from repro import suite

    names, import_errors = load_registry()
    if args.pattern_file:
        from repro.suite.spatter_io import SpatterParseError, trace_workload

        try:
            tw = suite.register(trace_workload(args.pattern_file))
        except SpatterParseError as e:
            sys.exit(f"--pattern-file rejected ({e.reason}): {e}")
        if tw.name not in names:
            names.append(tw.name)
    only = set(args.only.split(",")) if args.only else None
    tags = set(args.tag.split(",")) if args.tag else None

    def tag_selected(name: str) -> bool:
        if tags is None:
            return True
        try:
            w = suite.workload(name)
        except KeyError:
            # import-failed custom module: its tags are unknowable, so
            # keep it selected — a broken module must fail loud, not
            # silently pass a tagged smoke run
            return True
        return bool(tags & set(w.tags))

    def selected(name: str, figure: str = "") -> bool:
        named = (only is None or name in only or figure in only
                 or name.split("_")[0] in only)
        return named and tag_selected(name)

    if args.list:
        for name in names:
            if not selected(name, suite.workload(name).figure):
                continue
            wtags = ",".join(suite.workload(name).tags)
            print(f"{name}" + (f"  [{wtags}]" if wtags else ""))
        return

    from repro.core.errors import BenchFailure

    journal_dir = pathlib.Path(args.journal) if args.journal else None
    if journal_dir is not None:
        journal_dir.mkdir(parents=True, exist_ok=True)

    if args.jobs < 1:
        sys.exit(f"--jobs must be >= 1, got {args.jobs}")
    exec_backend = (suite.ThreadPoolBackend(args.jobs)
                    if args.jobs > 1 else None)

    print("name,us_per_call,derived")
    # structured failure entries: {workload, stage, error, point?, message}
    failures: list[dict] = []
    # structured --backend skip entries: {workload, backend, reason}
    skipped: list[dict] = []
    # demotion-ladder steps: {workload, variant, labels, step, stage, error}
    demotions: list[dict] = []
    module_seconds: dict[str, float] = {}
    # per-workload stage/measure wall-time split from the plan engine
    module_phases: dict[str, dict] = {}
    for name, err in import_errors.items():
        if not selected(name):
            continue
        failures.append({"workload": name, "stage": "import",
                         "error": err.split(":", 1)[0], "message": err})
        module_seconds[name] = 0.0
        print(f"# {name} FAILED at import: {err}", flush=True)
    t_suite = time.time()
    import dataclasses

    for name in names:
        w = suite.workload(name)
        if not selected(name, w.figure):
            continue
        if args.backend:
            if w.runner is not None:
                skipped.append({"workload": name, "backend": args.backend,
                                "reason": "custom_runner"})
                print(f"# {name} SKIPPED for --backend {args.backend}: "
                      "custom runner", flush=True)
                continue
            reason = (_pallas_ineligible(w, quick=not args.full)
                      if args.backend == "pallas" else None)
            if reason is not None:
                skipped.append({"workload": name, "backend": args.backend,
                                "reason": reason})
                print(f"# {name} SKIPPED for --backend {args.backend}: "
                      f"{reason}", flush=True)
                continue
            w = dataclasses.replace(w, variants=tuple(
                dataclasses.replace(v, backend=args.backend)
                for v in w.variant_list(not args.full)))
        t0 = time.time()
        journal = (str(journal_dir / f"{name}.jsonl")
                   if journal_dir is not None and w.runner is None else None)
        stats: dict = {}
        demoted: list[dict] = []
        try:
            suite.run_workload(w, quick=not args.full, journal=journal,
                               backend=exec_backend, executor_stats=stats,
                               demotions=demoted)
            module_seconds[name] = round(time.time() - t0, 3)
            print(f"# {name} done in {module_seconds[name]:.1f}s", flush=True)
        except BenchFailure as e:
            # the engine already isolated the faults per point and emitted
            # every surviving row; record the per-point entries and move on
            module_seconds[name] = round(time.time() - t0, 3)
            point_failures = getattr(e, "failures", None)
            if point_failures:
                for f in point_failures:
                    failures.append({
                        "workload": name, "stage": f.stage, "error": f.error,
                        "point": f"{f.variant}/{f.label}",
                        "message": f.message,
                    })
            else:
                failures.append({"workload": name, "stage": e.stage,
                                 "error": type(e).__name__,
                                 "message": str(e)})
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
        except Exception as e:  # noqa: BLE001
            module_seconds[name] = round(time.time() - t0, 3)
            failures.append({"workload": name, "stage": "run",
                             "error": type(e).__name__, "message": str(e)})
            print(f"# {name} FAILED: {type(e).__name__}: {e}", flush=True)
        demotions.extend({"workload": name, **d} for d in demoted)
        if stats:  # declarative workloads: the engine's phase split
            module_phases[name] = {
                k: (round(v, 3) if isinstance(v, float) else v)
                for k, v in stats.items()
            }

    # aggregate executor accounting across the batch (sum of the
    # per-workload plan-engine runs; custom runners contribute nothing)
    executor = {
        "backend": exec_backend.name if exec_backend is not None else "serial",
        "workers": exec_backend.workers if exec_backend is not None else 1,
        "workloads": len(module_phases),
        "stage_seconds": round(sum(
            p.get("stage_seconds", 0.0) for p in module_phases.values()), 3),
        "measure_seconds": round(sum(
            p.get("measure_seconds", 0.0) for p in module_phases.values()), 3),
        "stage_wall_seconds": round(sum(
            p.get("stage_wall_seconds", 0.0)
            for p in module_phases.values()), 3),
        "staging_overlap_seconds": round(sum(
            p.get("staging_overlap_seconds", 0.0)
            for p in module_phases.values()), 3),
        "wall_seconds": round(sum(
            p.get("wall_seconds", 0.0) for p in module_phases.values()), 3),
    }

    if args.smoke:
        from repro.core.staging import GLOBAL_CACHE

        try:
            probe = _param_path_probe()
        except Exception as e:  # noqa: BLE001 - a broken probe must gate
            probe = {"error": f"{type(e).__name__}: {e}"}
        try:
            pallas_probe = _pallas_probe()
        except Exception as e:  # noqa: BLE001 - a broken probe must gate
            pallas_probe = {"error": f"{type(e).__name__}: {e}"}
        # provenance of the application-derived workloads that ran:
        # mined source op + feature vector, with per-workload failure flag
        try:
            from repro.suite.derived import derived_report

            failed_names = {f["workload"] for f in failures}
            derived_block = {
                name: {**info, "failed": name in failed_names}
                for name, info in derived_report(
                    names=set(module_seconds)).items()
            }
        except Exception as e:  # noqa: BLE001 - a broken block must gate
            derived_block = {"error": f"{type(e).__name__}: {e}"}
        # provenance + live bit-exact replay check for every trace
        # workload that ran (builtin spatter_ms1 and --pattern-file)
        try:
            from repro.suite.spatter_io import trace_report

            failed_names = {f["workload"] for f in failures}
            trace_block = {
                name: {**info, "failed": name in failed_names}
                for name, info in trace_report(
                    names=set(module_seconds)).items()
            }
        except Exception as e:  # noqa: BLE001 - a broken block must gate
            trace_block = {"error": f"{type(e).__name__}: {e}"}
        # the contention study: re-measure the quick mix sweep and gate
        # on the per-pattern byte split + the isolated-vs-contended gap
        try:
            from repro.suite.catalog import contended_probe
            from repro.suite.runner import collect_records

            if "mess_contended" in module_seconds:
                contended_block = contended_probe([
                    r for _, r in collect_records(
                        suite.workload("mess_contended"), quick=True)])
            else:
                contended_block = {"skipped": "mess_contended not selected"}
        except Exception as e:  # noqa: BLE001 - a broken block must gate
            contended_block = {"error": f"{type(e).__name__}: {e}"}
        ledger = {
            "suite": "benchmarks.run --smoke",
            "mode": "full" if args.full else "quick",
            "backend": args.backend or "jax",
            "total_seconds": round(time.time() - t_suite, 3),
            "module_seconds": module_seconds,
            "module_phases": module_phases,
            "executor": executor,
            "failures": failures,
            "demotions": demotions,
            "skipped": skipped,
            "translation_cache": GLOBAL_CACHE.stats(),
            "param_path_probe": probe,
            "pallas_probe": pallas_probe,
            "derived": derived_block,
            "trace": trace_block,
            "contended": contended_block,
        }
        out = pathlib.Path(args.out)
        out.write_text(json.dumps(ledger, indent=2) + "\n")
        print(f"# wrote {out}", flush=True)

    if failures:
        names_failed = sorted({f["workload"] for f in failures})
        sys.exit(
            f"{len(failures)} failure(s) across {len(names_failed)} "
            f"workload(s): {', '.join(names_failed)}")


if __name__ == "__main__":
    main()
