"""Plain reference of the 5-point Jacobi 2-D step in jax.numpy.

``a[i, j] = (b[i-1, j] + b[i+1, j] + b[i, j-1] + b[i, j+1] + b[i, j]) / 5``
on the interior ``1 <= i, j < n - 1`` of each program's ``n x n`` grid;
the rim of ``a``, and all of ``b``, must stay as they were.
"""
from __future__ import annotations

import numpy as np

import jax.numpy as jnp
from jax import lax


def reference(x: dict, n, cfg: dict, rnd) -> dict:
    """``rnd`` rounds every input and every result to the precision the
    reference computes in."""
    a, b = rnd(x["A"]), rnd(x["B"])
    i = lax.broadcasted_iota(jnp.int32, b.shape, 1)
    j = lax.broadcasted_iota(jnp.int32, b.shape, 2)
    interior = (i >= 1) & (i < n - 1) & (j >= 1) & (j < n - 1)
    # neighbours as shifted views of one zero-padded copy (rotations would
    # materialize four copies of the grids); the rim is masked out
    bp = jnp.pad(b, ((0, 0), (1, 1), (1, 1)))
    terms = (bp[:, :-2, 1:-1], bp[:, 2:, 1:-1], bp[:, 1:-1, :-2],
             bp[:, 1:-1, 2:], b)
    fifth = rnd(jnp.float32(np.float32(1.0 / 5.0)))
    total = terms[0]
    for t in terms[1:]:
        total = rnd(total + t)
    mag = sum(jnp.abs(t) for t in terms)
    return {
        "A": (jnp.where(interior, rnd(total * fifth), a),
              jnp.where(interior, mag * fifth, 0.0)),
        "B": (b, None),
    }


def traffic_bytes(cfg: dict, n: int) -> dict:
    """Compulsory HBM bytes of one call: each grid read once, its
    interior written once (about 8 B per point in f32)."""
    programs, n = int(cfg["driver_config"]["programs"]), int(n)
    return {"hbm": 4 * programs * (n * n + (n - 2) * (n - 2))}
