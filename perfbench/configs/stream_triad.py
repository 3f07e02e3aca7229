"""Plain reference of STREAM triad, ``a = b + s * c``, in jax.numpy.

The independent template holds each stream as ``(programs, capacity)``
rows; a rung of row extent ``n`` computes ``a`` on ``[:, :n)`` and must
leave the rest of ``a``, and all of ``b`` and ``c``, as they were.
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def reference(x: dict, n, cfg: dict, rnd) -> dict:
    """``rnd`` rounds every input and every result to the precision the
    reference computes in."""
    s = rnd(jnp.float32(cfg["pattern_args"]["scalar"]))
    a, b, c = (rnd(x[k]) for k in ("A", "B", "C"))
    inside = lax.broadcasted_iota(jnp.int32, a.shape, a.ndim - 1) < n
    sc = rnd(s * c)
    return {
        "A": (jnp.where(inside, rnd(b + sc), a),
              jnp.where(inside, jnp.abs(b) + jnp.abs(sc), 0.0)),
        "B": (b, None),
        "C": (c, None),
    }


def traffic_bytes(cfg: dict, n: int) -> dict:
    """Compulsory HBM bytes of one call: ``b`` and ``c`` read once and
    ``a`` written once over the rung, STREAM's 12 B per point in f32."""
    points = int(cfg["driver_config"]["programs"]) * int(n)
    return {"hbm": 3 * 4 * points}
