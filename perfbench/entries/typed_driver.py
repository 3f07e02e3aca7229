"""Entry for patterns whose spaces differ in type: the program's ``Driver``.

As ``driver``, it stages the ladder through ``Driver(<pattern>,
DriverConfig(...)).prepare`` and drives each rung through
``Prepared.executable()``; the pattern comes from ``module`` (default
``repro.core``). Each space is drawn on the device in its own dtype:
uniform(-1, 1) times its ``draw.scale`` (1 where none is given), from a
key folded from the rung's key by the space's position. A space whose
init the pattern fixes by the env (an ``Affine`` in the params, such as
a decode position ``n - 1``) takes that value instead. A space named in
``draw.layered`` is drawn a layer (its leading index) at a time, each
from its own key, into one buffer filled in place, so that the check can
draw a layer again and compare the program's answer one layer at a time:
a cache of several GB is never held twice.

The check compares, layer by layer, the spaces the reference returns
(the pattern's written spaces) with ``reference(inputs, n, cfg, rnd)``
on that layer's inputs (``compare.compare``).
"""
from __future__ import annotations


def draw(key, shape, dtype, scale: float = 1.0):
    """uniform(-1, 1) times ``scale``, held in ``dtype``. A narrower
    float is rounded first by ``reduce_precision`` to its own bits, so
    the value is the one the cast stores even where the check widens it
    again inside one computation. XLA may drop a float32 -> bfloat16 ->
    float32 round trip there (``xla_allow_excess_precision``, on by
    default; the TPU compiler does), and the check would then compare
    the stored cache with float32 values the program never held."""
    import jax
    import jax.numpy as jnp

    v = jax.random.uniform(key, shape, jnp.float32, -1.0, 1.0) * scale
    dtype = jnp.dtype(dtype)
    if jnp.issubdtype(dtype, jnp.floating) and dtype.itemsize < 4:
        fi = jnp.finfo(dtype)
        v = jax.lax.reduce_precision(v, exponent_bits=fi.nexp,
                                     mantissa_bits=fi.nmant)
    return v.astype(dtype)


def build(cfg: dict, ref, traffic: dict, seed: int):
    import functools
    import importlib

    import jax
    import jax.numpy as jnp

    import repro.core as core
    from repro.core.domain import Affine

    from perfbench.compare import bfloat16, compare, exact, worst_of
    from perfbench.harness import Call, Cell, seed_key

    dcfg = dict(cfg["driver_config"])
    if int(dcfg.get("ntimes", 0)) != 1:
        raise ValueError("compulsory bytes are counted for ntimes=1 only")
    module = importlib.import_module(cfg.get("module", "repro.core"))
    factory = getattr(module, cfg["pattern"])
    args = dict(cfg.get("pattern_args", {}))
    driver = core.Driver(lambda env: factory(**args), core.DriverConfig(**dcfg),
                         cache=core.TranslationCache())
    preps = driver.prepare([int(n) for n in traffic["n"]], parallel=False)
    lowereds = {id(p.lowered): p.lowered.lower_seconds for p in preps}
    compileds = {id(p.compiled.executable): p.compiled.compile_seconds
                 for p in preps}
    stage_s = sum(lowereds.values()) + sum(compileds.values())

    drawing = cfg.get("draw", {})
    layered = frozenset(drawing.get("layered", ()))
    scales = {k: float(v) for k, v in drawing.get("scale", {}).items()}

    def specs(p):
        """``(name, shape, dtype, fixed)`` per space in the executable's
        order; ``fixed`` is the value the env sets, or None."""
        out = []
        for k in p.compiled.names:
            s = p.lowered.pattern.space(k)
            fixed = (s.init.eval(p.lowered.env) if isinstance(s.init, Affine)
                     else None)
            out.append((k, s.concrete_shape(p.lowered.env),
                        jnp.dtype(s.dtype), fixed))
        return tuple(out)

    @functools.partial(jax.jit, static_argnums=(1, 2, 3))
    def whole(key, shape, dtype, scale):
        return draw(key, shape, dtype, scale)

    @functools.partial(jax.jit, static_argnums=(3,), donate_argnums=(0,))
    def fill(buf, key, layer, scale):
        part = draw(jax.random.fold_in(key, layer), buf.shape[1:],
                    buf.dtype, scale)
        return jax.lax.dynamic_update_index_in_dim(buf, part, layer, 0)

    def make(key, spec):
        """Every space of one rung, drawn from ``key``."""
        arrays = []
        for i, (name, shape, dtype, fixed) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            scale = scales.get(name, 1.0)
            if fixed is not None:
                arrays.append(jnp.full(shape, fixed, dtype))
            elif name in layered:
                buf = jnp.zeros(shape, dtype)
                for layer in range(shape[0]):
                    buf = fill(buf, k, layer, scale)
                arrays.append(buf)
            else:
                arrays.append(whole(k, shape, dtype, scale))
        return tuple(arrays)

    def layer_inputs(key, layer, spec):
        """The inputs of one layer, drawn again as ``make`` drew them:
        layered spaces hold that layer alone (a leading axis of 1)."""
        x = {}
        for i, (name, shape, dtype, fixed) in enumerate(spec):
            k = jax.random.fold_in(key, i)
            scale = scales.get(name, 1.0)
            if fixed is not None:
                x[name] = jnp.full(shape, fixed, dtype)
            elif name in layered:
                x[name] = draw(jax.random.fold_in(k, layer), shape[1:],
                               dtype, scale)[None]
            else:
                x[name] = draw(k, shape, dtype, scale)
        return x

    key = seed_key(seed)
    keys = [jax.random.fold_in(key, r) for r in range(len(preps))]
    spec_of = [specs(p) for p in preps]

    held: list[dict] = [{} for _ in preps]
    calls = []
    for p, k, spec, box in zip(preps, keys, spec_of, held):
        fn = p.executable()
        tup = make(k, spec)
        n = int(p.env["n"])
        calls.append(Call(label=f"n={n}", run=lambda fn=fn, tup=tup: fn(tup),
                          keep=lambda out, box=box: box.__setitem__("out", out),
                          nbytes=ref.traffic_bytes(cfg, n)))
        del tup

    def got_layer(got, layer):
        return {k: (jax.lax.dynamic_slice_in_dim(v, layer, 1, 0)
                    if k in layered else v) for k, v in got.items()}

    @functools.partial(jax.jit, static_argnums=(2, 4))
    def check_program(k, layer, spec, got, n):
        x = layer_inputs(k, layer, spec)
        want = ref.reference(x, n, cfg, exact)
        return compare(got_layer({w: got[w] for w in want}, layer), want)

    @functools.partial(jax.jit, static_argnums=(2, 3))
    def check_control(k, layer, spec, n):
        x = layer_inputs(k, layer, spec)
        want = ref.reference(x, n, cfg, exact)
        ctl = {name: w for name, (w, _) in
               ref.reference(x, n, cfg, bfloat16).items()}
        return compare(ctl, want)

    def check(use_control: bool = False):
        """Every rung's last answer against the reference, a layer at a
        time (or, with ``use_control``, the bfloat16 control in the
        program's place)."""
        limit = float(cfg["limits"]["max_rel_err"])
        worst, mism, failed = 0.0, 0, 0
        for r, p in enumerate(preps):
            n, spec = int(p.env["n"]), spec_of[r]
            layers = max((sh[0] for name, sh, _, _ in spec if name in layered),
                         default=1)
            got = (None if use_control
                   else dict(zip(p.compiled.names, held[r].pop("out"))))
            r_worst, r_mism = 0.0, 0
            for layer in range(layers):
                if use_control:
                    rel, mm = check_control(keys[r], layer, spec, n)
                else:
                    rel, mm = check_program(keys[r], layer, spec, got, n)
                r_worst = worst_of(r_worst, float(rel))
                r_mism += int(mm)
            del got
            failed += not (r_worst <= limit and r_mism == 0)
            worst = worst_of(worst, r_worst)
            mism += r_mism
        return [("max_rel_err", worst, limit),
                ("exact_mismatches", mism, 0)], failed

    return Cell(calls=calls, stage_s=stage_s, devices=jax.devices()[:1],
                check=check)
