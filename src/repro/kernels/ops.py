"""Jit'd public wrappers over the Pallas kernels.

These are what the benchmarks, drivers, and model code call. Each wrapper
validates shapes, dispatches dtype, and jits with static block/factor
arguments so re-invocations with the same geometry hit the compile cache.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import stream as _stream
from . import stencil as _stencil

__all__ = [
    "triad",
    "nstream",
    "triad_interleaved",
    "jacobi1d",
    "jacobi2d",
    "jacobi3d",
    "jacobi3d_streaming",
]


@partial(jax.jit, static_argnames=("scalar", "block", "interpret"))
def triad(b: jnp.ndarray, c: jnp.ndarray, *, scalar: float = 3.0,
          block: int = 4096, interpret: bool | None = None) -> jnp.ndarray:
    return _stream.stream(
        lambda bb, cc: bb + scalar * cc, b, c, block=block, interpret=interpret
    )


@partial(jax.jit, static_argnames=("scalar", "block", "interpret"))
def nstream(streams: tuple[jnp.ndarray, ...], *, scalar: float = 3.0,
            block: int = 4096, interpret: bool | None = None) -> jnp.ndarray:
    """A = scalar*S0 + S1 + ... (k concurrent read streams, paper Fig. 7)."""
    def combine(*vals):
        acc = vals[0] * scalar
        for v in vals[1:]:
            acc = acc + v
        return acc

    return _stream.stream(combine, *streams, block=block, interpret=interpret)


@partial(jax.jit, static_argnames=("scalar", "factor", "block", "interpret"))
def triad_interleaved(b: jnp.ndarray, c: jnp.ndarray, *, scalar: float = 3.0,
                      factor: int = 2, block: int = 1024,
                      interpret: bool | None = None) -> jnp.ndarray:
    return _stream.interleaved(
        lambda bb, cc: bb + scalar * cc, b, c,
        factor=factor, block=block, interpret=interpret,
    )


@partial(jax.jit, static_argnames=("block", "interpret"))
def jacobi1d(b: jnp.ndarray, *, block: int = 1024,
             interpret: bool | None = None) -> jnp.ndarray:
    return _stencil.jacobi1d_blocked(b, block=block, interpret=interpret)


@partial(jax.jit, static_argnames=("block", "points", "interpret"))
def jacobi2d(b: jnp.ndarray, *, block: tuple[int, int] = (128, 128),
             points: int = 5, interpret: bool | None = None) -> jnp.ndarray:
    return _stencil.jacobi2d_blocked(
        b, block=block, points=points, interpret=interpret
    )


@partial(jax.jit, static_argnames=("block", "interpret"))
def jacobi3d(b: jnp.ndarray, *, block: tuple[int, int, int] = (8, 8, 128),
             interpret: bool | None = None) -> jnp.ndarray:
    return _stencil.jacobi3d_blocked(b, block=block, interpret=interpret)


@partial(jax.jit, static_argnames=("block", "interpret"))
def jacobi3d_streaming(b: jnp.ndarray, *, block: tuple[int, int] = (8, 128),
                       interpret: bool | None = None) -> jnp.ndarray:
    return _stencil.jacobi3d_streaming(b, block=block, interpret=interpret)
