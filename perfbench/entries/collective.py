"""Entry for collective configurations: the program's jitted collective.

Takes the op that ``repro.suite.collectives`` builds over
``repro.launch.mesh.make_sweep_mesh(k)`` by name (an import error is the
loud failure when it moves), lowers and compiles it once per rung for
the sharded input, and calls the compiled op on an input made on the
devices from the seed. The op does not donate, so every call's result
is fresh: the check compares the last one and a few drawn from the seed,
on every device that holds a copy.
"""
from __future__ import annotations

import time

SAMPLED = 3          # window calls kept per rung besides the last
SAMPLE_SPAN = 64     # drawn from the first SAMPLE_SPAN calls


def build(cfg: dict, ref, traffic: dict, seed: int):
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_sweep_mesh
    from repro.suite.collectives import _sharded_ops

    from perfbench.compare import (compare, control, make_inputs, want,
                                   worst_of)
    from perfbench.harness import Call, Cell, seed_key

    k = int(cfg["devices"])
    mesh = make_sweep_mesh(k)
    op = _sharded_ops(mesh)[cfg["op"]]
    sharding = NamedSharding(mesh, P("device"))
    key = seed_key(seed)
    rng = np.random.default_rng(seed)

    stage_s = 0.0
    calls, rungs = [], []
    for r, n in enumerate(int(n) for n in traffic["n"]):
        shapes = (("x", (k * n,)),)
        t0 = time.perf_counter()
        exe = op.lower(jax.ShapeDtypeStruct((k * n,), jnp.float32,
                                            sharding=sharding)).compile()
        stage_s += time.perf_counter() - t0
        kr = jax.random.fold_in(key, r)
        x = jax.jit(lambda kr, sh=shapes: make_inputs(kr, sh)["x"],
                    out_shardings=sharding)(kr)
        rung = {"n": n, "key": kr, "shapes": shapes, "seen": 0, "kept": [],
                "last": None,
                "draw": set(rng.choice(SAMPLE_SPAN, SAMPLED, replace=False))}

        def keep(out, rung=rung):
            if rung["seen"] in rung["draw"]:
                rung["kept"].append(out)
            rung["last"] = out
            rung["seen"] += 1

        rungs.append(rung)
        calls.append(Call(label=f"n={n}", run=lambda exe=exe, x=x: exe(x),
                          keep=keep, nbytes=ref.traffic_bytes(cfg, n)))

    def expected(kr, n, sh):
        return want(ref.reference, make_inputs(kr, sh), n, cfg)

    check_program = jax.jit(
        lambda kr, n, got, sh: compare({"out": got}, expected(kr, n, sh)),
        static_argnums=3)
    check_control = jax.jit(
        lambda kr, n, sh: compare(
            control(ref.reference, make_inputs(kr, sh), n, cfg),
            expected(kr, n, sh)),
        static_argnums=2)

    def check(use_control: bool = False):
        """Each kept result, on every device's copy, against the sum of
        the shards drawn again on that device (or, with ``use_control``,
        the bfloat16 control in the program's place)."""
        limit = float(cfg["limits"]["max_rel_err"])
        worst, mism, failed = 0.0, 0, 0
        for rung in rungs:
            n, sh = rung["n"], rung["shapes"]
            readings = []
            if use_control:
                for d in mesh.devices.flat:
                    readings.append(check_control(
                        jax.device_put(rung["key"], d), n, sh))
            else:
                outs = rung.pop("kept") + [rung.pop("last")]
                for out in outs:
                    if out is None or out.shape != (n,):
                        readings.append((float("nan"), n))
                        continue
                    for shard in out.addressable_shards:
                        kd = jax.device_put(rung["key"], shard.device)
                        readings.append(check_program(kd, n, shard.data, sh))
            bad = False
            for rel, mm in readings:
                rel, mm = float(rel), int(mm)
                bad |= not (rel <= limit and mm == 0)
                worst = worst_of(worst, rel)
                mism += mm
            failed += bad
        return [("max_rel_err", worst, limit),
                ("exact_mismatches", mism, 0)], failed

    return Cell(calls=calls, stage_s=stage_s,
                devices=list(mesh.devices.flat), check=check)
