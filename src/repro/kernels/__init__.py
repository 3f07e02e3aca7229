"""Pallas TPU kernels for the memory-pattern hot spots.

stream.py   — STREAM/triad family + the paper's interleaving, BlockSpec-tiled
stencil.py  — Jacobi 1D/2D/3D, blocked + streaming (partial-block) variants
ops.py      — jit'd public wrappers (what benchmarks and models call)
ref.py      — pure-jnp oracles for allclose validation

All kernels are written for the TPU target (pl.pallas_call + BlockSpec,
native-tile-aligned blocks). Their ``interpret`` flag defaults to the
platform (``codegen.pallas_platform_mode()``): compiled on the chip, the
interpreter on XLA:CPU, where the tests validate them.
"""
from . import ops, ref  # noqa: F401
