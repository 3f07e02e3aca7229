"""Entry for memory-pattern configurations: the program's ``Driver``.

Stages the ladder through ``Driver(<pattern>, DriverConfig(...))
.prepare``, the program's normal entry, with the regime and emitter
left to its own choice, and drives each rung through
``Prepared.executable()``: donated buffers threaded from call to call.
Each rung holds its own capacity-shaped tuple, made on the device from
the seed, as ``Driver.measure_point`` allocates one per point.
"""
from __future__ import annotations


def build(cfg: dict, ref, traffic: dict, seed: int):
    import jax

    import repro.core as core

    from perfbench.compare import (compare, control, make_inputs, want,
                                   worst_of)
    from perfbench.harness import Call, Cell, seed_key

    dcfg = dict(cfg["driver_config"])
    if int(dcfg.get("ntimes", 0)) != 1:
        raise ValueError("compulsory bytes are counted for ntimes=1 only")
    factory = getattr(core, cfg["pattern"])
    args = dict(cfg.get("pattern_args", {}))
    driver = core.Driver(lambda env: factory(**args), core.DriverConfig(**dcfg),
                         cache=core.TranslationCache())
    preps = driver.prepare([int(n) for n in traffic["n"]], parallel=False)
    lowereds = {id(p.lowered): p.lowered.lower_seconds for p in preps}
    compileds = {id(p.compiled.executable): p.compiled.compile_seconds
                 for p in preps}
    stage_s = sum(lowereds.values()) + sum(compileds.values())

    shapes = [tuple((k, p.lowered.pattern.space(k)
                     .concrete_shape(p.lowered.env))
                    for k in p.compiled.names) for p in preps]
    key = seed_key(seed)
    keys = [jax.random.fold_in(key, r) for r in range(len(preps))]
    tuples = jax.jit(lambda ks: [
        tuple(make_inputs(k, sh).values()) for k, sh in zip(ks, shapes)])(keys)

    held: list[dict] = [{} for _ in preps]
    calls = []
    for p, tup, box in zip(preps, tuples, held):
        fn = p.executable()
        n = int(p.env["n"])
        calls.append(Call(label=f"n={n}", run=lambda fn=fn, tup=tup: fn(tup),
                          keep=lambda out, box=box: box.__setitem__("out", out),
                          nbytes=ref.traffic_bytes(cfg, n)))
    del tuples

    def expected(k, n, sh):
        return want(ref.reference, make_inputs(k, sh), n, cfg)

    check_program = jax.jit(
        lambda k, n, got, sh: compare(got, expected(k, n, sh)),
        static_argnums=3)
    check_control = jax.jit(
        lambda k, n, sh: compare(
            control(ref.reference, make_inputs(k, sh), n, cfg),
            expected(k, n, sh)),
        static_argnums=2)

    def check(use_control: bool = False):
        """Every rung's last answer against the reference (or, with
        ``use_control``, the bfloat16 control in the program's place)."""
        limit = float(cfg["limits"]["max_rel_err"])
        worst, mism, failed = 0.0, 0, 0
        for r, p in enumerate(preps):
            n = int(p.env["n"])
            if use_control:
                rel, mm = check_control(keys[r], n, shapes[r])
            else:
                got = dict(zip(p.compiled.names, held[r].pop("out")))
                rel, mm = check_program(keys[r], n, got, shapes[r])
                del got
            rel, mm = float(rel), int(mm)
            failed += not (rel <= limit and mm == 0)
            worst = worst_of(worst, rel)
            mism += mm
        return [("max_rel_err", worst, limit),
                ("exact_mismatches", mism, 0)], failed

    return Cell(calls=calls, stage_s=stage_s, devices=jax.devices()[:1],
                check=check)
