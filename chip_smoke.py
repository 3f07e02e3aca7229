#!/usr/bin/env python3
"""Bring-up smoke of the sweep engine on a TPU: the main path, once.

    python3 chip_smoke.py              # phases a-c on one chip
    python3 chip_smoke.py --chips 4    # the collective ladder on 4 chips, alone

Run from the repository root; the script puts ``src`` on the path itself.
One process drives the chip and starts no other. Each phase prints one
JSON line per measured point (device, rung, GB/s from the pattern's byte
accounting, compile seconds and misses, disk-cache hits and misses, and
whether the oracle matched); the last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase raises, and the script exits non-zero without that line.
Without a TPU it refuses to run.

Phases:

a. XLA backend at HBM scale through the public ``Driver``: a triad
   ladder sharing one parametric executable (top rung 3 GiB), and a
   jacobi2d step over two 1 GiB grids; each matched against the numpy
   oracle (``Driver.validate`` at the smallest rung, bit-exact at the top).
b. The generic Pallas emitters, compiled, through the sweep engine
   (single points, and a two-rung ladder on the parametric emitter):
   every record ``pallas_mode == "compiled"`` and no demotion; an
   over-VMEM kernel refused before Mosaic with a typed ``LowerFailure``.
c. The hand-written ``repro.kernels`` Pallas kernels at chip-legal tiles
   against ``repro.kernels.ref``.
d. (``--chips 4`` only) ``measure_collectives`` on a 4-device mesh:
   ring accounting against HLO bytes, and values against numpy.

The phase functions take their sizes as arguments, so the tests run them
tiny on the CPU.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
SRC = ROOT / "src"

# phase sizes on the chip (f32 elements per stream, grid edges)
TRIAD_LADDER = (1 << 24, 1 << 26, 1 << 28)   # top rung: 3 x 1 GiB
JACOBI2D_N = 16386                            # 2 x 1 GiB grids
PALLAS_TRIAD_N = 1 << 20
PALLAS_JACOBI2D_N = 258
PALLAS_OVER_VMEM_N = 1 << 24
KERNEL_STREAM_N = 1 << 26
KERNEL_GRID_N = 258
COLLECTIVE_SIZES = (1 << 10, 1 << 16, 1 << 20, 1 << 24)  # per device
PROGRAMS = 4


def _emit(row: dict) -> dict:
    print(json.dumps(row), flush=True)
    return row


def _device() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _disk_delta(before: dict) -> dict:
    from repro.core.staging import disk_cache_stats

    now = disk_cache_stats()
    return {"disk_hits": now["hits"] - before["hits"],
            "disk_misses": now["misses"] - before["misses"]}


def _record_row(phase: str, rung: int, rec, misses: int, disk0: dict,
                match: str) -> dict:
    return _emit({
        "phase": phase, "device": _device(), "rung": rung,
        "gbs": rec.gbs, "seconds": rec.seconds,
        "compile_seconds": rec.extra["compile_seconds"],
        "compile_misses": misses, **_disk_delta(disk0),
        "param_path": rec.extra["param_path"],
        **({"pallas_mode": rec.extra["pallas_mode"]}
           if "pallas_mode" in rec.extra else {}),
        "oracle": match,
    })


def _bitexact_top(driver, prepared, ntimes: int) -> None:
    """Run the timed executable of the top rung once more on fresh arrays
    and compare every space, bit for bit, with the numpy oracle."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import serial_oracle

    env = prepared.env
    arrays = prepared.lowered.pattern.allocate(prepared.lowered.env)
    names = prepared.compiled.names
    out = prepared.executable()(tuple(jnp.asarray(arrays[k]) for k in names))
    spec = driver.lower(env)
    want = serial_oracle(spec.pattern, spec.nest, arrays, env, ntimes=ntimes)
    for k, got in zip(names, out):
        region = tuple(slice(0, d) for d in want[k].shape)
        if not np.array_equal(np.asarray(got)[region], want[k]):
            raise AssertionError(
                f"{spec.pattern.name}: space {k} differs from the numpy "
                f"oracle at n={env['n']}")


def phase_xla(triad_ladder=TRIAD_LADDER, grid_n=JACOBI2D_N, *,
              programs: int = PROGRAMS, ntimes: int = 4,
              reps: int = 3) -> list[dict]:
    """(a) XLA backend through ``Driver``. ``triad_ladder`` counts f32
    elements per stream; the independent template gives each of
    ``programs`` rows a share of it."""
    from repro.core import (Driver, DriverConfig, TranslationCache,
                            jacobi2d, triad)
    from repro.core.staging import disk_cache_stats

    rows = []
    cache, disk0 = TranslationCache(), disk_cache_stats()
    d = Driver(lambda env: triad(), DriverConfig(
        template="independent", programs=programs, parametric="auto",
        ntimes=ntimes, reps=reps), cache=cache)
    per_row = [n // programs for n in triad_ladder]
    recs = d.run(per_row)
    d.validate({"n": per_row[0]})
    _bitexact_top(d, d.prepare(per_row, parallel=False)[-1], ntimes)
    misses = cache.stats()["compile_misses"]
    if misses != 1:
        raise AssertionError(f"triad ladder compiled {misses} executables, "
                             "expected 1 shared one")
    for n, rec in zip(triad_ladder, recs):
        rows.append(_record_row("a.triad", n, rec, misses, disk0,
                                "validated+bitexact"))

    # one grid pair of edge grid_n: the independent template with one
    # program (every space keeps a leading axis of 1)
    cache, disk0 = TranslationCache(), disk_cache_stats()
    d = Driver(lambda env: jacobi2d(), DriverConfig(
        template="independent", programs=1, parametric="auto",
        ntimes=ntimes, reps=reps), cache=cache)
    rec, = d.run([grid_n])
    d.validate({"n": grid_n})
    _bitexact_top(d, d.prepare([grid_n], parallel=False)[0], ntimes)
    rows.append(_record_row("a.jacobi2d", grid_n, rec,
                            cache.stats()["compile_misses"], disk0,
                            "validated+bitexact"))
    return rows


def phase_pallas(triad_n=PALLAS_TRIAD_N, grid_n=PALLAS_JACOBI2D_N,
                 over_vmem_n=PALLAS_OVER_VMEM_N, *,
                 programs: int = PROGRAMS, ntimes: int = 4, reps: int = 3,
                 mode: str = "compiled") -> list[dict]:
    """(b) The generic Pallas emitters through ``run_plan``: the records
    must carry ``pallas_mode == mode`` and the report no demotion."""
    from repro.core import (DriverConfig, LowerFailure, TranslationCache,
                            identity, independent_view, jacobi2d,
                            lower_pallas, triad)
    from repro.core.staging import disk_cache_stats
    from repro.suite import SweepPlan, VariantSpec, env_axis, run_plan

    rows = []
    per_row = triad_n // programs
    # single points lower through the generic emitter; the two-rung
    # ladder shares one lower_pallas_parametric executable
    for name, factory, ns, parametric in (
            ("b.triad", triad, (per_row,), None),
            ("b.jacobi2d", jacobi2d, (grid_n,), None),
            ("b.triad_ladder", triad, (per_row // 4, per_row), "auto")):
        cache, disk0 = TranslationCache(), disk_cache_stats()
        cfg = DriverConfig(template="independent", programs=programs,
                           backend="pallas", ntimes=ntimes, reps=reps,
                           validate_n=ns[0], parametric=parametric)
        report = run_plan(lambda env, f=factory: f(),
                          (VariantSpec("pallas", cfg),),
                          SweepPlan.product(env_axis(ns)), cache=cache)
        if report.failures or report.demotions:
            raise AssertionError(
                f"{name}: failures {[f.as_dict() for f in report.failures]}"
                f" demotions {report.demotions}")
        misses = cache.stats()["compile_misses"]
        if parametric and misses != 1:
            raise AssertionError(f"{name}: {misses} compiles for one ladder")
        for row in report.rows:
            got = row.record.extra.get("pallas_mode")
            if got != mode:
                raise AssertionError(f"{name}: pallas_mode {got!r}, "
                                     f"expected {mode!r}")
            n = row.record.n
            rung = n * programs if factory is triad else n
            rows.append(_record_row(name, rung, row.record, misses, disk0,
                                    "validated"))

    # operands over the VMEM budget never reach Mosaic
    env = {"n": over_vmem_n // programs}
    try:
        lower_pallas(independent_view(triad(), programs), identity(), env,
                     mode=mode, grid_bands=("p",))
    except LowerFailure as e:
        if e.context.get("reason") != "vmem":
            raise
        rows.append(_emit({"phase": "b.over_vmem", "device": _device(),
                           "rung": over_vmem_n, "refused": str(e),
                           "vmem_bytes": e.context["vmem_bytes"]}))
    else:
        raise AssertionError("an over-VMEM triad lowered without refusal")
    return rows


def phase_kernels(stream_n=KERNEL_STREAM_N, grid_n=KERNEL_GRID_N, *,
                  block=(8, 128), reps: int = 3) -> list[dict]:
    """(c) ``repro.kernels`` at chip-legal tiles against ``kernels.ref``."""
    import jax
    import numpy as np

    from repro.core import jacobi3d, triad
    from repro.core.measure import time_fn
    from repro.core.staging import disk_cache_stats
    from repro.kernels import ops, ref

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    b = jax.random.normal(k1, (stream_n,), jax.numpy.float32)
    c = jax.random.normal(k2, (stream_n,), jax.numpy.float32)
    g = jax.random.normal(k1, (grid_n,) * 3, jax.numpy.float32)
    stream_bytes = triad().bytes_per_point() * stream_n
    grid_bytes = jacobi3d().bytes_per_point() * (grid_n - 2) ** 3
    cases = (
        ("c.triad", stream_n, stream_bytes,
         lambda: ops.triad.lower(b, c), (b, c), ref.triad_ref(b, c)),
        ("c.triad_interleaved", stream_n, stream_bytes,
         lambda: ops.triad_interleaved.lower(b, c), (b, c),
         ref.triad_ref(b, c)),
        ("c.jacobi3d_streaming", grid_n, grid_bytes,
         lambda: ops.jacobi3d_streaming.lower(g, block=block), (g,),
         ref.jacobi3d_ref(g)),
    )
    rows = []
    for name, rung, nbytes, lower, args, want in cases:
        disk0 = disk_cache_stats()
        t0 = time.perf_counter()
        exe = lower().compile()
        compile_s = time.perf_counter() - t0
        np.testing.assert_allclose(np.asarray(exe(*args)), np.asarray(want),
                                   rtol=3e-5, atol=3e-5, err_msg=name)
        t = time_fn(exe, *args, reps=reps, warmup=1)
        rows.append(_emit({
            "phase": name, "device": _device(), "rung": rung,
            "gbs": nbytes / t.seconds / 1e9, "seconds": t.seconds,
            "compile_seconds": compile_s, "compile_misses": 1,
            **_disk_delta(disk0), "oracle": "allclose(kernels.ref)",
        }))
    return rows


def phase_collectives(sizes=COLLECTIVE_SIZES, *, devices: int = 4,
                      reps: int = 3) -> list[dict]:
    """(d) The collective ladder on a ``devices``-wide mesh: ring bytes
    against HLO bytes within 10%, and values against numpy."""
    from repro.launch.mesh import make_sweep_mesh
    from repro.suite.collectives import measure_collectives

    rows = measure_collectives(mesh=make_sweep_mesh(devices), sizes=sizes,
                               reps=reps)
    if len(rows) != 2 * len(sizes):
        raise AssertionError(f"collective ladder measured {len(rows)} "
                             f"points on {devices} devices")
    for r in rows:
        _emit({"phase": "d.collectives", "device": _device(), **r})
        if abs(r["agreement"] - 1.0) > 0.10 or not r["values_ok"]:
            raise AssertionError(f"collective point failed: {r}")
    return rows


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the collective ladder on 4 chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no repro package under {SRC}; run it "
                 "from a checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.core.staging import enable_persistent_cache

    enable_persistent_cache(str(ROOT / "experiments" / ".jax_cache"))
    dev = _device()
    if dev["platform"] != "tpu":
        sys.exit(f"chip_smoke.py: no TPU found (jax.devices()[0] is "
                 f"{dev['platform']!r}); this smoke runs on the chip only")
    if dev["count"] < args.chips:
        sys.exit(f"chip_smoke.py: --chips {args.chips} but "
                 f"{dev['count']} TPU device(s) visible")
    if args.chips == 4:
        phase_collectives()
    else:
        phase_xla()
        phase_pallas()
        phase_kernels()
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
