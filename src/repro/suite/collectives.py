"""Collective-bandwidth ladder — the device-sharded workload family.

AdaptMemBench characterizes a memory subsystem by driving it with
application-shaped traffic; on a sharded accelerator the interconnect
*is* part of that subsystem, and the traffic shapes that exercise it
are the collectives. This module measures an all-gather / all-reduce
size ladder sharded across the 1-D sweep mesh
(:func:`repro.launch.mesh.make_sweep_mesh` — on CPU CI the mesh comes
from ``--xla_force_host_platform_device_count``, the
``launch/dryrun.py`` / ``tests/test_system.py`` pattern) and validates
every point's bytes-on-the-wire two ways:

* **ring accounting** from the op and shapes alone
  (:func:`expected_wire_bytes` — all-gather moves ``(k-1)/k`` of the
  gathered result per device, all-reduce ``2(k-1)/k`` of the reduced
  buffer: reduce-scatter + all-gather);
* **HLO analysis** via
  :func:`repro.launch.hlo_analysis.analyze_collectives` over the
  compiled executable's text — the estimate the launch layer would make
  for a production program, finally exercised against a measured run.

The two must agree (CI gates at 10%); reported GB/s is aggregate wire
traffic (``k`` × per-device bytes) over the timed call.
"""
from __future__ import annotations

__all__ = [
    "COLLECTIVE_OPS",
    "collective_sizes",
    "expected_wire_bytes",
    "measure_collectives",
    "collective_runner",
]

COLLECTIVE_OPS = ("all_gather", "all_reduce")

# HLO op name per ladder op — what analyze_collectives keys its
# per-kind byte accounting on.
HLO_KIND = {"all_gather": "all-gather", "all_reduce": "all-reduce"}


def collective_sizes(quick: bool) -> tuple[int, ...]:
    """Per-device shard sizes (f32 elements) of the ladder."""
    return (1 << 10, 1 << 12) if quick else (1 << 10, 1 << 14, 1 << 16)


def expected_wire_bytes(op: str, shard_elems: int, k: int,
                        itemsize: int = 4) -> float:
    """Ring-accounting per-device wire bytes for ONE collective call
    over ``k`` devices holding ``shard_elems``-element shards.

    all_gather: every device receives the other ``k-1`` shards of the
    gathered ``k * shard_elems`` result — ``(k-1)/k`` of the result.
    all_reduce: reduce-scatter + all-gather over the ``shard_elems``
    buffer — ``2 (k-1)/k`` of it.
    """
    if op == "all_gather":
        return (k - 1) / k * (k * shard_elems * itemsize)
    if op == "all_reduce":
        return 2.0 * (k - 1) / k * (shard_elems * itemsize)
    raise ValueError(f"unknown collective op {op!r} "
                     f"(expected one of {COLLECTIVE_OPS})")


def _sharded_ops(mesh):
    """jit-wrapped shard_map bodies per op. ``check_vma=False`` is
    required: shard_map cannot statically infer that the collective
    results are replicated, and without it tracing raises."""
    import jax
    from jax.sharding import PartitionSpec as P

    def all_gather(x):
        return jax.lax.all_gather(x, "device", tiled=True)

    def all_reduce(x):
        return jax.lax.psum(x, "device")

    kw = dict(mesh=mesh, in_specs=P("device"), out_specs=P(None),
              check_vma=False)
    return {
        "all_gather": jax.jit(jax.shard_map(all_gather, **kw)),
        "all_reduce": jax.jit(jax.shard_map(all_reduce, **kw)),
    }


def measure_collectives(quick: bool = True, *, mesh=None, reps: int = 3,
                        sizes: "tuple[int, ...] | None" = None) -> list[dict]:
    """Run the ladder; one dict per (op, shard size) point.

    Keys: ``op``, ``devices``, ``shard_elems``, ``wire_bytes`` (ring
    accounting, per device), ``hlo_bytes`` (analyze_collectives, per
    device), ``agreement`` (hlo / ring), ``values_ok`` (the gathered or
    reduced result against numpy), ``seconds``, ``gbs`` (aggregate wire
    GB/s). ``sizes`` overrides the per-device shard ladder of
    :func:`collective_sizes`. Empty on a <2-device mesh — there is no
    wire to measure.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.core.measure import time_fn
    from repro.launch.hlo_analysis import analyze_collectives
    from repro.launch.mesh import make_sweep_mesh

    mesh = mesh if mesh is not None else make_sweep_mesh()
    k = int(mesh.devices.size)
    if k < 2:
        return []
    ops = _sharded_ops(mesh)
    out: list[dict] = []
    for op in COLLECTIVE_OPS:
        for s in (sizes or collective_sizes(quick)):
            x = jnp.linspace(0.0, 1.0, k * s, dtype=jnp.float32)
            compiled = ops[op].lower(x).compile()
            stats = analyze_collectives(compiled.as_text())
            hlo_bytes = stats.bytes_by_kind.get(HLO_KIND[op], 0.0)
            wire = expected_wire_bytes(op, s, k)
            got = np.asarray(compiled(x))
            host = np.asarray(x)
            if op == "all_gather":
                values_ok = bool(np.array_equal(got, host))
            else:  # the reduction order is the collective's own
                values_ok = bool(np.allclose(
                    got, host.reshape(k, s).sum(axis=0, dtype=np.float64),
                    rtol=1e-6, atol=1e-6))
            t = time_fn(compiled, x, reps=reps, warmup=1)
            out.append({
                "op": op,
                "devices": k,
                "shard_elems": s,
                "wire_bytes": wire,
                "hlo_bytes": hlo_bytes,
                "agreement": hlo_bytes / wire if wire else float("nan"),
                "values_ok": values_ok,
                "seconds": t.seconds,
                "gbs": k * wire / t.seconds / 1e9,
            })
    return out


def collective_runner(quick: bool = True) -> list[str]:
    """The registered workload entry: CSV lines per ladder point, with
    the ring-vs-HLO agreement verdict inline. A single-device box skips
    with a comment (the CI gate re-runs under a forced 8-device host
    platform)."""
    import jax

    from .runner import emit

    k = len(jax.devices())
    if k < 2:
        return emit([
            f"# collective ladder skipped: {k} device(s) visible — set "
            "--xla_force_host_platform_device_count (XLA_FLAGS) for a "
            "host mesh"
        ])
    rows = measure_collectives(quick)
    lines, bad = [], 0
    for r in rows:
        ok = abs(r["agreement"] - 1.0) <= 0.10 and r["values_ok"]
        bad += 0 if ok else 1
        lines.append(
            f"collective/{r['op']}/k{r['devices']}/s{r['shard_elems']},"
            f"{r['seconds'] * 1e6:.2f},{r['gbs']:.3f}GB/s,"
            f"wire={int(r['wire_bytes'])}B,hlo={int(r['hlo_bytes'])}B,"
            f"{'ok' if ok else 'MISMATCH'}"
        )
    if bad:
        lines.append(
            f"# collective ring-vs-hlo byte or value mismatch on {bad} "
            "point(s)")
    return emit(lines)
