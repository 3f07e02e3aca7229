"""One decode step of multi-head latent attention (MLA) as a registry
pattern: the model's own ``mla_apply`` over a latent KV cache.

``mla_decode(arch, layers, batch)`` reads every width from the
``ArchConfig`` registered as ``arch`` (DeepSeek-V2-Lite by default) and
holds ``layers`` MLA layers of ``batch`` sequences. Its rung parameter
``n`` is the context length T. One call is one decode step of every held
layer: each sequence stands at position ``pos = n - 1``; each layer
appends its row (the RMS-normed latent and the rotated rope key) through
``mla_apply``'s own ``dynamic_update_slice`` into the stacked, donated
cache of ``lm.init_cache``'s layout, ``ckv (L, B, T, c)`` and
``krope (L, B, T, r)``; attends over all T rows in the absorbed form;
and writes its output ``h[l]`` of shape ``(B, 1, d)``. Every layer takes
the same input ``x``: nothing between the layers is modelled. Calls
rewrite the same row with the same values, so the step is idempotent.

Storage is bfloat16 (cache, weights, activations) with float32
accumulation and softmax inside ``mla_apply``; the position is int32.
The cache row writes run in the scope ``repro.mla.append``, the scan
over the cache in ``repro.mla.scan`` and the projections in
``repro.mla.project`` (``models/attention.py``).
"""
from __future__ import annotations

import functools
from typing import Mapping

import numpy as np

from repro.core import DriverConfig, PatternSpec
from repro.core.domain import Affine, domain
from repro.core.pattern import Access, DataSpace, Statement

from .axes import SweepPlan, env_axis
from .registry import register
from .workload import VariantSpec, Workload

__all__ = ["mla_decode", "mla_weight_shapes", "register_decode"]

DTYPE = "bfloat16"
# the spaces the step writes; the weights, x and pos are read-only
WRITES = ("ckv", "krope", "h")


def _arch(arch):
    from repro.config import ArchConfig, get_config

    cfg = arch if isinstance(arch, ArchConfig) else get_config(arch)
    if cfg.mla is None:
        raise ValueError(f"{cfg.name!r} has no MLA attention")
    return cfg


@functools.lru_cache(maxsize=None)
def mla_weight_shapes(arch) -> tuple[tuple[str, tuple[int, ...]], ...]:
    """``(name, shape)`` of one layer's attention parameters, as
    ``mla_init`` makes them (the model's own parameter tree)."""
    import jax

    from repro.models.attention import mla_init

    cfg = _arch(arch)
    tree = jax.eval_shape(lambda k: mla_init(k, cfg.d_model, cfg.n_heads,
                                             cfg.mla),
                          jax.random.PRNGKey(0))
    return tuple(sorted((k, tuple(v.shape)) for k, v in tree.items()))


def _uniform(seed: int, scale: float = 1.0):
    """Position-stable pseudo-random values in ``[-scale, scale)``: the
    value at an index does not depend on the allocation's size."""
    def init(*grids):
        h = np.zeros(grids[0].shape, np.int64)
        for g in grids:
            h = (h * 1000033 + g) % 2147483647
        u = ((h * 1103515245 + seed) % 1000003) / 1000003.0
        return ((2.0 * u - 1.0) * scale).astype(np.float32)
    return init


def _kernel(cfg, layers: int, batch: int):
    def kernel(pattern: PatternSpec, env: Mapping[str, int]):
        import jax
        import jax.numpy as jnp

        from repro.models.attention import mla_apply

        weights = [k for k, _ in mla_weight_shapes(cfg)]

        def step(arrays):
            arrays = dict(arrays)
            start = arrays["pos"][0]
            positions = jnp.full((batch, 1), start, jnp.int32)
            ckv, krope, hs = arrays["ckv"], arrays["krope"], []
            for l in range(layers):
                with jax.named_scope("repro.mla.append"):
                    cache = {"ckv": ckv[l], "krope": krope[l], "len": start}
                h, new = mla_apply(
                    {k: arrays[k][l] for k in weights}, arrays["x"],
                    n_heads=cfg.n_heads, mla=cfg.mla, positions=positions,
                    rope_theta=cfg.rope_theta, cache=cache,
                    absorbed_decode=True)
                with jax.named_scope("repro.mla.append"):
                    ckv = ckv.at[l].set(new["ckv"])
                    krope = krope.at[l].set(new["krope"])
                hs.append(h)
            arrays.update(ckv=ckv, krope=krope,
                          h=jnp.stack(hs).astype(arrays["h"].dtype))
            return arrays

        return step

    return kernel


def _oracle(cfg, layers: int):
    """Numpy ground truth in float64: the decompressed form (keys and
    values expanded from the latent), the plain softmax, then W_o."""
    m = cfg.mla
    H, dn, dr, dv, c = (cfg.n_heads, m.qk_nope_head_dim, m.qk_rope_head_dim,
                        m.v_head_dim, m.kv_lora_rank)

    def rope(v, pos):
        half = v.shape[-1] // 2
        freq = cfg.rope_theta ** (-np.arange(half, dtype=np.float64) / half)
        ang = pos * freq
        a, b = v[..., :half], v[..., half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], axis=-1)

    def oracle(pattern, arrays, env, ntimes):
        out = {k: np.array(v) for k, v in arrays.items()}
        f = {k: np.asarray(v, np.float64) for k, v in arrays.items()}
        pos = int(f["pos"][0])
        x = f["x"][:, 0]                                     # (B, d)
        B = x.shape[0]
        h = np.empty(out["h"].shape, np.float64)
        for l in range(layers):
            q = (x @ f["w_q"][l]).reshape(B, H, dn + dr)
            q_nope, q_rope = q[..., :dn], rope(q[..., dn:], pos)
            dkv = x @ f["w_dkv"][l]
            lat = dkv[:, :c]
            lat = lat / np.sqrt(np.mean(lat * lat, -1, keepdims=True) + 1e-6)
            lat = lat * (1.0 + f["kv_norm"][l])
            out["ckv"][l, :, pos] = lat
            out["krope"][l, :, pos] = rope(dkv[:, c:], pos)
            ckv = np.asarray(out["ckv"][l, :, :pos + 1], np.float64)
            kr = np.asarray(out["krope"][l, :, :pos + 1], np.float64)
            k = (ckv @ f["w_uk"][l]).reshape(B, -1, H, dn)
            v = (ckv @ f["w_uv"][l]).reshape(B, -1, H, dv)
            s = (np.einsum("bhd,bthd->bht", q_nope, k)
                 + np.einsum("bhr,btr->bht", q_rope, kr)) / np.sqrt(dn + dr)
            p = np.exp(s - s.max(-1, keepdims=True))
            o = np.einsum("bht,bthd->bhd", p / p.sum(-1, keepdims=True), v)
            h[l, :, 0] = o.reshape(B, H * dv) @ f["w_o"][l]
        out["h"] = h.astype(out["h"].dtype)
        return out

    return oracle


def mla_decode(arch="deepseek-v2-lite-16b", layers: int = 1,
               batch: int = 1) -> PatternSpec:
    """One MLA decode step of ``layers`` layers and ``batch`` sequences
    at the published widths of ``arch`` (an arch id or an ArchConfig);
    the rung parameter ``n`` is the context length."""
    cfg = _arch(arch)
    m = cfg.mla
    L, B, d = int(layers), int(batch), cfg.d_model
    row = m.kv_lora_rank + m.qk_rope_head_dim
    weights = mla_weight_shapes(cfg)
    spaces = (
        DataSpace("ckv", (L, B, "n", m.kv_lora_rank), DTYPE, _uniform(1)),
        DataSpace("krope", (L, B, "n", m.qk_rope_head_dim), DTYPE,
                  _uniform(2)),
        DataSpace("h", (L, B, 1, d), DTYPE, 0.0),
        DataSpace("x", (B, 1, d), DTYPE, _uniform(3)),
        DataSpace("pos", (1,), "int32", Affine.of("n") - 1),
    ) + tuple(
        DataSpace(name, (L,) + shape, DTYPE,
                  _uniform(11 + i, 1.0 / np.sqrt(shape[0])))
        for i, (name, shape) in enumerate(weights)
    )
    itemsize = np.dtype(DTYPE).itemsize

    def traffic(env):
        """Every space read or written once, and the appended rows."""
        held = sum(int(np.prod(s.concrete_shape(env)))
                   * np.dtype(s.dtype).itemsize for s in spaces)
        return held + L * B * row * itemsize

    # accounting only (the kernel replaces the statement): one cache row
    # read per position; ``traffic`` gives the bytes
    stmt = Statement(
        reads=(Access("ckv", (0, 0, "i", 0)), Access("krope", (0, 0, "i", 0))),
        write=Access("h", (0, 0, 0, 0)),
        combine=lambda vals, env: vals[0],
    )
    # flops per position: scores over c + r and the combine over c, per
    # head, layer and sequence (the projections are O(1) in n)
    fpp = L * B * cfg.n_heads * 2 * (row + m.kv_lora_rank)
    return PatternSpec(
        name=f"mla_decode.{cfg.name}.L{L}.B{B}",
        spaces=spaces,
        statement=stmt,
        domain=domain(("i", 0, "n")),
        flops_per_point=fpp,
        kernel=_kernel(cfg, L, B),
        oracle=_oracle(cfg, L),
        writes=WRITES,
        traffic=traffic,
        # bfloat16 storage: a stored value may sit one bf16 step (2^-8
        # relative) from the float64 oracle, and h carries the rounding
        # of q, p and the attention output before W_o
        tolerance=(2e-2, 5e-4),
    )


_DECODE_CFG = DriverConfig(template="unified", programs=1, ntimes=2,
                           reps=2, validate_n=64)


def register_decode() -> None:
    """Register the MLA decode step (idempotent): DeepSeek-V2-Lite's
    published widths, one layer of two sequences, contexts up to 4k."""
    register(Workload(
        name="derived_mla_decode",
        figure="derived",
        title="DeepSeek-V2-Lite MLA decode step over a latent KV cache",
        tags=("derived", "app"),
        pattern=lambda env: mla_decode("deepseek-v2-lite-16b", 1, 2),
        variants=(VariantSpec("decode", _DECODE_CFG),),
        plan=SweepPlan.product(env_axis((256, 1024), (256, 1024, 4096))),
        parametric=False,       # custom kernel: env is baked into the step
    ))
