"""Paper Fig. 16 — spatial tile-size sweep for Jacobi 3D.

The paper sweeps 2D (partial) blocking tiles 16..64 and finds no win on
large-cache CPUs. The TPU adaptation sweeps the (bj, bk) output-tile
shape of the blocked Pallas kernel AND compares the xyz-blocked kernel
against the streaming (partial-block) kernel, whose halo traffic model is
derived in kernels/stencil.py. Derived column = achieved GB/s (CPU
interpret numbers; the structural result — streaming >= xyz at equal
tiles, driven by halo re-reads — is substrate-independent).

Staged pipeline: every (kernel, tile) variant is lowered serially
(tracing is GIL-bound) and AOT-compiled concurrently (XLA releases the
GIL), then timing runs against the pre-compiled executables only —
translation cost never pollutes the measured numbers and is reported as
a comment line instead.

Tiles are chip-legal: the last two block dims are multiples of the
(8, 128) f32 tile or span the whole interior dim. A variant the compiler
refuses is reported as a failure (after the surviving rows), never
dropped.
"""
import time

import jax
import jax.numpy as jnp

from repro.core.errors import FailureRecord, SweepFailures
from repro.core.measure import time_fn
from repro.core.staging import pipeline_compile
from repro.kernels import ops
from repro.suite import Workload, emit, register, run_module


def _compile_or_error(lowered):
    try:
        return lowered.compile()
    except Exception as e:  # noqa: BLE001 - reported as a failure row
        return e


def _tile_sweep(quick: bool = True) -> list[str]:
    out = []
    n = 130 if quick else 258
    x = jax.random.normal(jax.random.PRNGKey(0), (n, n, n), jnp.float32)
    interior = (n - 2) ** 3
    bytes_moved = 2 * interior * 4
    sublane_tiles = [8, 16, 32] if quick else [8, 16, 32, 64]
    lane_tiles = [128] if quick else [128, 256]

    # stages 1+2, overlapped: lower each variant on the main thread
    # (tracing is GIL-bound) while finished lowerings compile on worker
    # threads (XLA releases the GIL), so translation wall-time is
    # ~max(lower, compile) instead of their sum.
    t0 = time.perf_counter()
    variants = []
    for bj in sublane_tiles:
        for bk in lane_tiles:
            variants.append((f"fig16/stream/b{bj}x{bk}",
                             lambda bj=bj, bk=bk: ops.jacobi3d_streaming.lower(
                                 x, block=(bj, bk))))
            variants.append((f"fig16/xyz/b8x{bj}x{bk}",
                             lambda bj=bj, bk=bk: ops.jacobi3d.lower(
                                 x, block=(8, bj, bk))))
    compiled = pipeline_compile([lower for _, lower in variants],
                                compile_fn=_compile_or_error)
    translate_s = time.perf_counter() - t0

    # stage 3: execute + time the pre-compiled executables
    failures = []
    for (label, _), exe in zip(variants, compiled):
        if isinstance(exe, Exception):
            failures.append(FailureRecord(
                variant=label, label=f"n{n}", stage="compile",
                error="CompileFailure",
                message=f"{type(exe).__name__}: {exe}"))
            continue
        t = time_fn(exe, x, reps=2, warmup=1)
        out.append(f"{label},{t.seconds*1e6:.2f},"
                   f"{bytes_moved/t.seconds/1e9:.3f}GB/s")
    print(f"# fig16 staged: {len(variants)} variants, "
          f"lower+compile {translate_s:.2f}s (overlapped)", flush=True)
    emit(out)
    if failures:
        raise SweepFailures(failures)
    return out


# Fully custom experiment (dedicated Pallas kernels, not the driver
# templates): registers a ``runner`` and shares the registry surface.
register(Workload(
    name="fig16_tile_sweep",
    figure="fig16",
    title="spatial tile-size sweep for the blocked Jacobi-3D kernels",
    tags=("paper-figs",),
    runner=_tile_sweep,
))


def run(quick: bool = True) -> list[str]:
    return run_module("fig16_tile_sweep", quick)
