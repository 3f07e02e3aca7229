"""Blocked Pallas kernels for the STREAM/triad pattern family.

These are the BlockSpec-tiled showcase versions of the patterns the
generic ``repro.core.codegen`` backend lowers in manual-DMA style. Block
shapes default to multiples of the v5e native tile (8x128 f32 = 1024
elements) so the MXU/VPU sees hardware-aligned operands. ``interpret=None``
(every kernel's default) resolves through
``codegen.pallas_platform_mode()``: compiled on an accelerator, the
interpreter on XLA:CPU only.

Kernels:

``stream``          A = f(B, C, ...) elementwise over 1D arrays, blocked
                    into ``block``-element VMEM tiles (copy/scale/sum/triad
                    and the k-read-stream generalization of paper Fig. 7).

``interleaved``     the paper's triad interleaving (Listing 7) as a layout
                    transformation: arrays are viewed as (factor, n/factor)
                    and blocks span all ``factor`` rows, so each grid step
                    streams ``factor`` segments of every operand
                    simultaneously — 2*factor+1 concurrent DMA streams for
                    triad, the TPU analogue of "use more prefetch streams".
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.codegen import pallas_platform_mode

__all__ = ["stream", "interleaved", "NATIVE_BLOCK", "resolve_interpret"]

NATIVE_BLOCK = 8 * 128  # one f32 native tile, flattened


def resolve_interpret(interpret: bool | None) -> bool:
    """An explicit flag wins; ``None`` follows the platform."""
    if interpret is None:
        return pallas_platform_mode() == "interpret"
    return interpret


def _check(n: int, block: int) -> None:
    if n % block != 0:
        raise ValueError(f"block {block} must divide n {n}")


def stream(
    combine: Callable[..., jnp.ndarray],
    *streams: jnp.ndarray,
    block: int = 4 * NATIVE_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """A[i] = combine(streams...[i]) with 1D BlockSpec tiling.

    ``combine`` receives one ``(block,)`` array per input stream.
    """
    n = streams[0].shape[0]
    for s in streams:
        if s.shape != (n,):
            raise ValueError("all streams must be 1D of equal length")
    block = min(block, n)
    _check(n, block)
    grid = (n // block,)

    def kernel(*refs):
        *ins, out = refs
        out[...] = combine(*[r[...] for r in ins]).astype(out.dtype)

    spec = pl.BlockSpec((block,), lambda i: (i,))
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(streams),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n,), streams[0].dtype),
        interpret=resolve_interpret(interpret),
    )(*streams)


def interleaved(
    combine: Callable[..., jnp.ndarray],
    *streams: jnp.ndarray,
    factor: int = 2,
    block: int = NATIVE_BLOCK,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Interleaved-by-``factor`` stream: each grid step touches ``factor``
    disjoint segments of every operand at once (paper Listing 7).

    Input 1D arrays of length n are *viewed* (no copy — XLA reshape of a
    contiguous array is a bitcast) as (factor, n//factor); a (factor, block)
    BlockSpec then walks all segments in lockstep.
    """
    n = streams[0].shape[0]
    if n % factor != 0:
        raise ValueError(f"factor {factor} must divide n {n}")
    seg = n // factor
    block = min(block, seg)
    if seg % block != 0:
        raise ValueError(f"block {block} must divide segment {seg}")
    grid = (seg // block,)

    def kernel(*refs):
        *ins, out = refs
        out[...] = combine(*[r[...] for r in ins]).astype(out.dtype)

    spec = pl.BlockSpec((factor, block), lambda i: (0, i))
    out2d = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec] * len(streams),
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((factor, seg), streams[0].dtype),
        interpret=resolve_interpret(interpret),
    )(*[s.reshape(factor, seg) for s in streams])
    return out2d.reshape(n)
