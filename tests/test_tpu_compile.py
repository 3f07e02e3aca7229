"""Compile the chip path's Pallas kernels for a described TPU v5e.

Nothing runs: each test AOT-compiles a kernel at the size ``chip_smoke.py``
drives on the chip, against a ``v5e:2x2`` topology described (not
attached) inside a fixture, so Mosaic's refusals — tiling, alignment,
VMEM — surface here at no chip time. The topology is built only after a
test of this file has started, never at import.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    LowerFailure,
    identity,
    independent_view,
    jacobi2d,
    lower_pallas,
    triad,
)
from repro.core.codegen import lower_pallas_parametric
from repro.kernels import ops

PROGRAMS = 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["triad", "triad_interleaved",
                                  "jacobi3d_streaming"])
def test_kernels_compile_at_smoke_sizes(one_chip, name):
    stream = _sds((1 << 26,), one_chip)
    grid = _sds((258, 258, 258), one_chip)
    lowered = {
        "triad": lambda: ops.triad.lower(stream, stream, interpret=False),
        "triad_interleaved": lambda: ops.triad_interleaved.lower(
            stream, stream, interpret=False),
        "jacobi3d_streaming": lambda: ops.jacobi3d_streaming.lower(
            grid, block=(8, 128), interpret=False),
    }[name]()
    _assert_kernel(lowered.compile())


@pytest.mark.parametrize("factory,n", [(triad, (1 << 20) // PROGRAMS),
                                       (jacobi2d, 258)])
def test_lower_pallas_compiles(one_chip, factory, n):
    pat = independent_view(factory(), PROGRAMS)
    env = {"n": n}
    step = lower_pallas(pat, identity(), env, mode="compiled",
                        grid_bands=("p",))
    assert step.pallas_mode == "compiled"
    arrays = {s.name: _sds(s.concrete_shape(env), one_chip, s.dtype)
              for s in pat.spaces}
    _assert_kernel(jax.jit(step).lower(arrays).compile())


def test_lower_pallas_parametric_compiles_triad_ladder(one_chip):
    """One executable for the ladder 2^12..2^16 per row: aligned lane
    windows of a chunk that tiles every rung."""
    pat = independent_view(triad(), PROGRAMS)
    cap_env = {"n": 1 << 16}
    step = lower_pallas_parametric(pat, identity(), cap_env, chunk=4096,
                                   assume_full=True, mode="compiled")
    assert step.param_path == "strided"
    arrays = {s.name: _sds(s.concrete_shape(cap_env), one_chip, s.dtype)
              for s in pat.spaces}
    pvals = (_sds((), one_chip, jnp.int32),)
    _assert_kernel(jax.jit(step).lower(arrays, pvals).compile())


def test_over_vmem_triad_refused_before_mosaic():
    pat = independent_view(triad(), PROGRAMS)
    env = {"n": (1 << 24) // PROGRAMS}
    with pytest.raises(LowerFailure) as ei:
        lower_pallas(pat, identity(), env, mode="compiled", grid_bands=("p",))
    ctx = ei.value.context
    assert ctx["reason"] == "vmem" and ctx["backend"] == "pallas"
    assert ctx["vmem_bytes"] == 3 * (1 << 24) * np.dtype(np.float32).itemsize
    assert str(ctx["vmem_bytes"]) in str(ei.value)


def test_mla_decode_step_compiles_at_published_widths(one_chip):
    """One layer of DeepSeek-V2-Lite's MLA decode step at mla.decode32k's
    batch and context: the written spaces alone donated, the cache
    aliased in place, and the three ``repro.mla.*`` scopes in the
    executable the device trace is read against."""
    from repro.core import spans
    from repro.suite.decode import mla_decode

    env = {"n": 32768}
    pat = mla_decode("deepseek-v2-lite-16b", 1, 24)
    step = pat.kernel(pat, env)
    names = sorted(s.name for s in pat.spaces)
    own = [k for k in names if k in pat.writes]
    rest = [k for k in names if k not in own]

    def sds(k):
        s = pat.space(k)
        return _sds(s.concrete_shape(env), one_chip, jnp.dtype(s.dtype))

    def fused(tup, ro):
        d = dict(zip(own, tup))
        d.update(zip(rest, ro))
        out = step(d)
        return tuple(out[k] for k in own)

    compiled = jax.jit(fused, donate_argnums=(0,)).lower(
        tuple(sds(k) for k in own), tuple(sds(k) for k in rest)).compile()
    cache = sum(int(np.prod(pat.space(k).concrete_shape(env))) * 2
                for k in ("ckv", "krope"))
    assert compiled.memory_analysis().alias_size_in_bytes >= cache
    _, scopes = spans.scope_map(compiled.as_text())
    assert {"repro.mla.project", "repro.mla.append",
            "repro.mla.scan"} <= set(scopes.values())
