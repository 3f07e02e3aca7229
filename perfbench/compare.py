"""Inputs from the seed, and the comparison that decides ``correct``.

Inputs are drawn on the device by ``make_inputs``; the check draws them
again from the same key inside its own jitted comparison (threefry is
counter-based, so the values are the same bits), which keeps the
reference independent of every array the program has held.

A configuration's ``reference(x, n, cfg, rnd)`` computes in float32 and
passes every input and every result through ``rnd``: ``exact`` for the
reference itself, ``bfloat16`` for the control. It returns, per output
space, ``(want, scale)``: ``scale`` is the magnitude an element's error
is measured against where the element is computed, and 0 (or ``None``
for the whole space) where it must come back bit for bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def make_inputs(key, shapes) -> dict:
    """``{name: uniform(-1, 1)}`` for ``[(name, shape), ...]``, each
    space from its own key folded from ``key`` by its position."""
    return {name: jax.random.uniform(jax.random.fold_in(key, i), shape,
                                     jnp.float32, -1.0, 1.0)
            for i, (name, shape) in enumerate(shapes)}


def compare(got: dict, want: dict):
    """``(max_rel_err, exact_mismatches)`` of ``got`` against ``want``:
    the largest ``|got - want| / scale`` over computed elements, and the
    count of elements that must be exact and are not. NaN reads as NaN
    and as a mismatch, so it fails either way."""
    worst = jnp.float32(0.0)
    mism = jnp.int32(0)
    for name, (w, scale) in want.items():
        g = got[name].astype(jnp.float32)
        w = w.astype(jnp.float32)
        differ = g != w
        if scale is None:
            mism += jnp.sum(differ, dtype=jnp.int32)
            continue
        computed = scale > 0
        err = jnp.abs(g - w) / jnp.where(computed, scale, 1.0)
        worst = jnp.maximum(worst, jnp.max(jnp.where(computed, err, 0.0)))
        mism += jnp.sum(differ & ~computed, dtype=jnp.int32)
    return worst, mism


def worst_of(a: float, b: float) -> float:
    """The larger reading, where NaN counts as the worst."""
    return float("nan") if a != a or b != b else max(a, b)


def exact(v):
    """The reference's own precision, float32: no rounding."""
    return v


def bfloat16(v):
    """Round to bfloat16 (8 exponent bits, 7 mantissa bits) and stay in
    float32. ``reduce_precision`` keeps XLA from folding the rounding
    away, as it may fold a float32 -> bfloat16 -> float32 round trip."""
    return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)


def want(reference, x: dict, n, cfg) -> dict:
    return reference(x, n, cfg, exact)


def control(reference, x: dict, n, cfg) -> dict:
    """The control in the program's place: the reference computed in
    bfloat16, the precision below the configurations' float32."""
    return {name: w for name, (w, _) in
            reference(x, n, cfg, bfloat16).items()}
