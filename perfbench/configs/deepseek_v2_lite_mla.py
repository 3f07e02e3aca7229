"""Plain reference of one MLA decode step, in jax.numpy and float32.

DeepSeek-V2's attention in its decompressed form (arXiv:2405.04434,
section 2.1), independent of the program's absorbed one: per layer,
``q = x W_q`` (nope and rope parts, RoPE on the rope part at the
position); the new latent ``c = RMSNorm(x W_dkv[:, :c])`` (the
kv_a_layernorm) and rope key ``RoPE(x W_dkv[:, c:])`` land in row
``pos`` of the cache; keys ``k = [c W_uk ; krope]`` and values
``v = c W_uv`` are expanded from every cached row, the softmax at scale
``1/sqrt(nope + rope)`` weighs them, and ``W_o`` maps the heads to ``h``.
No YaRN, in the program and here alike (the configuration's
``departures``).

The positions are taken in blocks with an online softmax, so that a
layer of 32k rows fits beside what the check holds. ``rnd`` rounds every
input and every partial result, each block's included: the bfloat16
control accumulates in bfloat16 across blocks. The blocks are short (256
of them at 32k), so the control rounds its running sums often, nearer to
bfloat16 accumulation, which rounds at every addition.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 128  # cached positions per block of the online softmax


def _widths(cfg: dict):
    return (int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"]),
            int(cfg["qk_rope_head_dim"]), int(cfg["v_head_dim"]),
            int(cfg["kv_lora_rank"]))


def _rope(v, pos, theta: float, rnd):
    half = v.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = v[..., :half], v[..., half:]
    return jnp.concatenate([rnd(rnd(a * cos) - rnd(b * sin)),
                            rnd(rnd(b * cos) + rnd(a * sin))], axis=-1)


def _layer(x, w, ckv, krope, pos, cfg: dict, rnd):
    """One layer for ``B`` sequences: ``x`` (B, d), the layer's weights
    ``w``, its cache ``ckv`` (B, T, c) and ``krope`` (B, T, r) before
    the step. Returns ``(h, new latent, new rope key)`` and their
    scales."""
    H, dn, dr, dv, c = _widths(cfg)
    B, T = ckv.shape[:2]
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])

    q = rnd(x @ w["w_q"]).reshape(B, H, dn + dr)
    q_nope, q_rope = q[..., :dn], _rope(q[..., dn:], pos, theta, rnd)
    dkv = rnd(x @ w["w_dkv"])
    # each new row element's magnitude: the sum of the magnitudes of the
    # terms of its projection, carried through the norm and the rotation
    dkv_mag = jnp.abs(x) @ jnp.abs(w["w_dkv"])
    lat = dkv[:, :c]
    inv = rnd(jax.lax.rsqrt(rnd(jnp.mean(rnd(lat * lat), -1, keepdims=True))
                            + eps))
    gain = rnd(1.0 + w["kv_norm"])
    lat_new = rnd(rnd(lat * inv) * gain)
    lat_mag = dkv_mag[:, :c] * inv * jnp.abs(gain)
    rope_new = _rope(dkv[:, c:], pos, theta, rnd)
    half = dr // 2
    rope_mag = jnp.tile(dkv_mag[:, c:c + half] + dkv_mag[:, c + half:], 2)

    blk = BLOCK
    while T % blk:
        blk -= 1
    scale = np.float32(1.0 / np.sqrt(dn + dr))
    w_uk = w["w_uk"].reshape(c, H, dn)
    w_uv = w["w_uv"].reshape(c, H, dv)

    def body(j, carry):
        m, l, acc = carry
        kpos = j * blk + jnp.arange(blk)
        new = (kpos == pos)[None, :, None]
        # the cache stays in its own type; a block at a time is widened,
        # with row ``pos`` replaced by the new row
        cb = jnp.where(new, lat_new[:, None], rnd(
            jax.lax.dynamic_slice_in_dim(ckv, j * blk, blk, 1)
            .astype(jnp.float32)))
        rb = jnp.where(new, rope_new[:, None], rnd(
            jax.lax.dynamic_slice_in_dim(krope, j * blk, blk, 1)
            .astype(jnp.float32)))
        k = rnd(jnp.einsum("btc,chd->bthd", cb, w_uk))
        v = rnd(jnp.einsum("btc,chd->bthd", cb, w_uv))
        s = rnd(rnd(rnd(jnp.einsum("bhd,bthd->bht", q_nope, k))
                    + rnd(jnp.einsum("bhr,btr->bht", q_rope, rb))) * scale)
        s = jnp.where(kpos[None, None, :] <= pos, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(-1))
        corr = rnd(jnp.exp(m - m_new))
        p = rnd(jnp.exp(s - m_new[..., None]))
        l = rnd(rnd(l * corr) + rnd(p.sum(-1)))
        acc = rnd(rnd(acc * corr[..., None])
                  + rnd(jnp.einsum("bht,bthd->bhd", p, v)))
        return m_new, l, acc

    m0 = jnp.full((B, H), -jnp.inf, jnp.float32)
    _, l, acc = jax.lax.fori_loop(
        0, T // blk, body, (m0, jnp.zeros((B, H), jnp.float32),
                            jnp.zeros((B, H, dv), jnp.float32)))
    out = rnd(acc / l[..., None]).reshape(B, H * dv)
    h = rnd(out @ w["w_o"])
    # h's magnitude: the RMS of its row. The softmax averages values of
    # both signs, so the sum of its terms' magnitudes would stand some
    # hundred times above the output it bounds
    h_rms = jnp.sqrt(jnp.mean(h * h, -1, keepdims=True)) * jnp.ones_like(h)
    return (h, h_rms), (lat_new, lat_mag), (rope_new, rope_mag)


def reference(x: dict, n, cfg: dict, rnd) -> dict:
    """The step over every layer of ``x`` (spaces as the program holds
    them, any number of layers on the leading axis). Per written space
    ``(want, scale)``: the cache comes back bit for bit but for row
    ``pos``, whose latent and rope key are computed, each against the
    sum of the magnitudes of the terms that make it; ``h`` is computed,
    against the RMS of its row."""
    with jax.default_matmul_precision("highest"):
        f = {k: (v if k in ("pos", "ckv", "krope")
                 else rnd(v.astype(jnp.float32))) for k, v in x.items()}
        pos = f["pos"][0]
        xs = f["x"][:, 0]
        L, B, T = x["ckv"].shape[:3]
        outs = [_layer(xs, {k: f[k][i] for k in ("w_q", "w_dkv", "kv_norm",
                                                  "w_uk", "w_uv", "w_o")},
                       x["ckv"][i], x["krope"][i], pos, cfg, rnd)
                for i in range(L)]
        row = (jnp.arange(T) == pos)[None, :, None]

        def cache(name, part):
            want = jnp.stack([jnp.where(row, o[part][0][:, None],
                                        x[name][i].astype(jnp.float32))
                              for i, o in enumerate(outs)])
            scale = jnp.stack([jnp.where(row, o[part][1][:, None], 0.0)
                               for o in outs])
            return want, scale

        return {
            "h": (jnp.stack([o[0][0] for o in outs])[:, :, None],
                  jnp.stack([o[0][1] for o in outs])[:, :, None]),
            "ckv": cache("ckv", 1),
            "krope": cache("krope", 2),
        }


def traffic_bytes(cfg: dict, n: int) -> dict:
    """Compulsory bytes of one call at context ``n``, in bf16: every
    layer's cache read whole (``cache_read``, what the scan must read),
    the appended rows written, the attention weights and ``x`` read,
    ``h`` written. ``hbm`` is their sum."""
    H, dn, dr, dv, c = _widths(cfg)
    d = int(cfg["hidden_size"])
    L = int(cfg["pattern_args"]["layers"])
    B = int(cfg["pattern_args"]["batch"])
    cache = L * B * int(n) * (c + dr) * 2
    weights = L * 2 * (d * H * (dn + dr) + d * (c + dr) + c
                       + c * H * dn + c * H * dv + H * dv * d)
    rest = L * B * (c + dr) * 2 + B * d * 2 + L * B * d * 2 + 4
    return {"hbm": cache + weights + rest, "cache_read": cache}
