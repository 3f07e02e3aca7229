"""Blocked Pallas kernels for the Jacobi stencil family (paper §III-B).

TPU adaptation of the paper's tiling study. The CPU version tiles to keep
working sets in L1/L2; the TPU version tiles so that (a) the output block
plus its halo'd input window fits VMEM, and (b) the trailing two dims are
native-tile aligned. Halos are handled the TPU-idiomatic way: the *output*
is blocked with a non-overlapping BlockSpec while the *input* stays
unblocked (whole-array ref = HBM-resident operand) and the kernel slices
the halo'd window explicitly — the manual-DMA pattern Mosaic compiles to
HBM->VMEM copies. Overlapping input windows cannot be expressed as a
blocked BlockSpec (blocks are disjoint by construction), which is exactly
why the paper's "blocking in all three dimensions" transliterates poorly
to TPU; see jacobi3d_streaming for the adaptation that works.

Kernels:
    jacobi1d_blocked     1D, grid over interior blocks.
    jacobi2d_blocked     5-pt/9-pt 2D, 2D grid of (bi, bj) output tiles.
    jacobi3d_blocked     7-pt 3D, 3D grid (the paper's xyz tiling).
    jacobi3d_streaming   7-pt 3D, 2D grid over (j,k) tiles; i is *streamed*
                         inside the kernel with a rolling 3-plane window —
                         the paper's "partial blocking" (Rivera-Tseng)
                         adapted to the TPU memory hierarchy.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from .stream import resolve_interpret

__all__ = [
    "jacobi1d_blocked",
    "jacobi2d_blocked",
    "jacobi3d_blocked",
    "jacobi3d_streaming",
]

_THIRD = np.float32(1.0 / 3.0)
_FIFTH = np.float32(1.0 / 5.0)
_SEVENTH = np.float32(1.0 / 7.0)


def _div(a: int, b: int, what: str) -> int:
    if a % b != 0:
        raise ValueError(f"{what}: {b} must divide {a}")
    return a // b


def jacobi1d_blocked(b: jnp.ndarray, *, block: int = 1024,
                     interpret: bool | None = None) -> jnp.ndarray:
    """A[i] = (B[i-1]+B[i]+B[i+1])/3 on 1 <= i < n-1; A keeps B's borders.

    Interior (n-2) must be divisible by ``block``. Output is blocked;
    input is an unblocked ref sliced with a halo of 1.
    """
    n = b.shape[0]
    interior = n - 2
    block = min(block, interior)
    nb = _div(interior, block, "jacobi1d interior")

    def kernel(b_ref, out_ref):
        i = pl.program_id(0)
        start = i * block + 1
        w = b_ref[pl.ds(start - 1, block + 2)]
        out_ref[...] = ((w[:-2] + w[1:-1] + w[2:]) * _THIRD).astype(out_ref.dtype)

    interior_out = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec(b.shape, lambda i: (0,))],  # whole array
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((interior,), b.dtype),
        interpret=resolve_interpret(interpret),
    )(b)
    return b.at[1:-1].set(interior_out)


def jacobi2d_blocked(b: jnp.ndarray, *, block: tuple[int, int] = (128, 128),
                     points: int = 5, interpret: bool | None = None) -> jnp.ndarray:
    """5-pt star or 9-pt box Jacobi 2D with a 2D grid of output tiles."""
    n0, n1 = b.shape
    bi = min(block[0], n0 - 2)
    bj = min(block[1], n1 - 2)
    gi = _div(n0 - 2, bi, "jacobi2d dim0")
    gj = _div(n1 - 2, bj, "jacobi2d dim1")

    def kernel(b_ref, out_ref):
        i = pl.program_id(0) * bi + 1
        j = pl.program_id(1) * bj + 1
        w = b_ref[pl.ds(i - 1, bi + 2), pl.ds(j - 1, bj + 2)]
        c = w[1:-1, 1:-1]
        if points == 5:
            acc = (w[:-2, 1:-1] + w[2:, 1:-1] + w[1:-1, :-2] + w[1:-1, 2:] + c)
            res = acc * _FIFTH
        else:  # 9-pt box
            acc = c
            for di in (0, 1, 2):
                for dj in (0, 1, 2):
                    if di == 1 and dj == 1:
                        continue
                    acc = acc + w[di:di + bi, dj:dj + bj]
            res = acc * np.float32(1.0 / 9.0)
        out_ref[...] = res.astype(out_ref.dtype)

    interior = pl.pallas_call(
        kernel,
        grid=(gi, gj),
        in_specs=[pl.BlockSpec(b.shape, lambda i, j: (0, 0))],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n0 - 2, n1 - 2), b.dtype),
        interpret=resolve_interpret(interpret),
    )(b)
    return b.at[1:-1, 1:-1].set(interior)


def jacobi3d_blocked(b: jnp.ndarray, *, block: tuple[int, int, int] = (8, 8, 128),
                     interpret: bool | None = None) -> jnp.ndarray:
    """7-pt Jacobi 3D, xyz tiling (paper Listing 9): 3D grid of tiles.

    Every tile re-fetches a (bi+2, bj+2, bk+2) halo'd window — the halo
    re-read overhead is (1+2/b)^3 - 1; with the paper's 16^3 tiles that is
    ~42% extra traffic, which is why xyz tiling loses. The roofline
    benchmark quantifies this; jacobi3d_streaming removes it.
    """
    n0, n1, n2 = b.shape
    bi, bj, bk = (min(bb, nn - 2) for bb, nn in zip(block, b.shape))
    gi = _div(n0 - 2, bi, "jacobi3d dim0")
    gj = _div(n1 - 2, bj, "jacobi3d dim1")
    gk = _div(n2 - 2, bk, "jacobi3d dim2")

    def kernel(b_ref, out_ref):
        i = pl.program_id(0) * bi + 1
        j = pl.program_id(1) * bj + 1
        k = pl.program_id(2) * bk + 1
        w = b_ref[pl.ds(i - 1, bi + 2), pl.ds(j - 1, bj + 2), pl.ds(k - 1, bk + 2)]
        c = w[1:-1, 1:-1, 1:-1]
        acc = (
            w[:-2, 1:-1, 1:-1] + w[2:, 1:-1, 1:-1]
            + w[1:-1, :-2, 1:-1] + w[1:-1, 2:, 1:-1]
            + w[1:-1, 1:-1, :-2] + w[1:-1, 1:-1, 2:]
            + c
        )
        out_ref[...] = (acc * _SEVENTH).astype(out_ref.dtype)

    interior = pl.pallas_call(
        kernel,
        grid=(gi, gj, gk),
        in_specs=[pl.BlockSpec(b.shape, lambda i, j, k: (0, 0, 0))],
        out_specs=pl.BlockSpec((bi, bj, bk), lambda i, j, k: (i, j, k)),
        out_shape=jax.ShapeDtypeStruct((n0 - 2, n1 - 2, n2 - 2), b.dtype),
        interpret=resolve_interpret(interpret),
    )(b)
    return b.at[1:-1, 1:-1, 1:-1].set(interior)


def jacobi3d_streaming(b: jnp.ndarray, *, block: tuple[int, int] = (8, 128),
                       interpret: bool | None = None) -> jnp.ndarray:
    """7-pt Jacobi 3D, partial (j,k) blocking with the i dim *streamed*.

    The TPU-native version of Rivera-Tseng partial blocking: a 2D grid of
    (bj, bk) column tiles; inside the kernel a fori_loop walks i planes
    keeping a rolling window of three (bj+2, bk+2) planes in registers /
    VMEM. Per-tile HBM traffic is (bj+2)(bk+2)/(bj*bk) of minimal — halo
    re-reads happen only in the two blocked dims, and each plane is read
    once, so the streamed dim is traffic-optimal.
    """
    n0, n1, n2 = b.shape
    bj = min(block[0], n1 - 2)
    bk = min(block[1], n2 - 2)
    gj = _div(n1 - 2, bj, "jacobi3d dim1")
    gk = _div(n2 - 2, bk, "jacobi3d dim2")

    def kernel(b_ref, out_ref):
        j = pl.program_id(0) * bj + 1
        k = pl.program_id(1) * bk + 1

        def plane(i):
            return b_ref[pl.ds(i, 1), pl.ds(j - 1, bj + 2), pl.ds(k - 1, bk + 2)][0]

        def body(i, carry):
            prev, cur = carry  # planes i-1 and i (full halo'd slabs)
            nxt = plane(i + 1)
            c = cur[1:-1, 1:-1]
            acc = (
                prev[1:-1, 1:-1] + nxt[1:-1, 1:-1]
                + cur[:-2, 1:-1] + cur[2:, 1:-1]
                + cur[1:-1, :-2] + cur[1:-1, 2:]
                + c
            )
            out_ref[pl.ds(i - 1, 1), :, :] = (acc * _SEVENTH).astype(
                out_ref.dtype
            )[None]
            return (cur, nxt)

        jax.lax.fori_loop(1, n0 - 1, body, (plane(0), plane(1)))

    interior = pl.pallas_call(
        kernel,
        grid=(gj, gk),
        in_specs=[pl.BlockSpec(b.shape, lambda j, k: (0, 0, 0))],
        out_specs=pl.BlockSpec((n0 - 2, bj, bk), lambda j, k: (0, j, k)),
        out_shape=jax.ShapeDtypeStruct((n0 - 2, n1 - 2, n2 - 2), b.dtype),
        interpret=resolve_interpret(interpret),
    )(b)
    return b.at[1:-1, 1:-1, 1:-1].set(interior)
