"""Attention variants: GQA (full / sliding-window / cross), MLA.

Compute paths:

* ``chunked_attention`` — pure-JAX online-softmax (flash-style) attention:
  a scan over KV chunks carrying (m, l, acc). Bounded memory at any
  sequence length, so the 32k prefill and 512k decode shapes compile with
  flat VMEM/HBM footprints. This is the dry-run/default path; GSPMD
  shards it over batch/heads (and sequence for long decode).
* ``repro.kernels`` hosts the Pallas blocked kernels for the perf study;
  the model picks per config (``attn_impl``).

MLA (DeepSeek/Kimi) implements both the decompressed (train/prefill) and
the absorbed (decode) forms; the KV cache stores only the compressed
``c_kv`` + shared rope key — the technique's whole point (cache is
(B, S, kv_lora + rope) instead of (B, S, 2*H*hd)).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .layers import DEFAULT_DTYPE, init_linear, rms_norm, rope

__all__ = [
    "gqa_init",
    "gqa_apply",
    "mla_init",
    "mla_apply",
    "chunked_attention",
]

NEG_INF = -1e30
# DeepSeek-V2's rms_norm_eps, used by the kv_a_layernorm over c_kv
KV_NORM_EPS = 1e-6


# ---------------------------------------------------------------------------
# Core: chunked online-softmax attention
# ---------------------------------------------------------------------------


def chunked_attention(
    q: jnp.ndarray,              # (B, Sq, H, D)
    k: jnp.ndarray,              # (B, Sk, Hkv, D)
    v: jnp.ndarray,              # (B, Sk, Hkv, Dv)
    *,
    causal: bool,
    q_offset: jnp.ndarray | int = 0,   # absolute position of q[0]
    window: int | jnp.ndarray = 0,     # 0 = unbounded; may be traced (gemma3)
    kv_chunk: int = 1024,
    q_chunk: int = 4096,
    scale: float | None = None,
    kv_positions: jnp.ndarray | None = None,  # (Sk,) — ring caches
) -> jnp.ndarray:
    """Flash-style attention: scan over query blocks of an inner scan over
    KV chunks. Both loops bound the live set — the (m, l, acc) running
    state is (B, q_chunk, H) shaped regardless of sequence length, which
    is what lets prefill_32k / long_500k compile with flat footprints.
    ``kv_positions`` overrides the implied arange positions for ring
    (sliding-window) caches whose slots are not in position order; unused
    slots carry a huge positive position so the causal mask drops them.
    Returns (B, Sq, H, Dv)."""
    B, Sq, H, D = q.shape
    if Sq > q_chunk:
        qc = q_chunk
        while Sq % qc:
            qc -= 1
        nq = Sq // qc
        qb = jnp.moveaxis(q.reshape(B, nq, qc, H, D), 1, 0)

        def q_body(_, inp):
            qj, j = inp
            out = _chunked_attention_inner(
                qj, k, v, causal=causal, q_offset=q_offset + j * qc,
                window=window, kv_chunk=kv_chunk, scale=scale,
                kv_positions=kv_positions)
            return None, out

        _, outs = jax.lax.scan(q_body, None, (qb, jnp.arange(nq)))
        return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, H, v.shape[-1])
    return _chunked_attention_inner(
        q, k, v, causal=causal, q_offset=q_offset, window=window,
        kv_chunk=kv_chunk, scale=scale, kv_positions=kv_positions)


def _chunked_attention_inner(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
    causal: bool, q_offset, window, kv_chunk: int, scale: float | None,
    kv_positions: jnp.ndarray | None = None,
) -> jnp.ndarray:
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    Dv = v.shape[-1]
    g = H // Hkv                                   # queries per KV head
    scale = (1.0 / np.sqrt(D)) if scale is None else scale

    kv_chunk = min(kv_chunk, Sk)
    while Sk % kv_chunk:
        kv_chunk -= 1
    n_chunks = Sk // kv_chunk

    # operands stay in their input dtype (bf16 on TPU); all reductions
    # accumulate in f32 via preferred_element_type — the flash recipe.
    # Heads stay FLAT: a (Hkv, g) reshape of a head-sharded query is not
    # representable in GSPMD (SPMD "involuntary full rematerialization"
    # per chunk); instead each KV chunk is broadcast to the query heads —
    # a local repeat of a VMEM-sized tile, free of collectives.
    qs = q * jnp.asarray(scale, q.dtype)           # (B,Sq,H,D)
    kc = jnp.moveaxis(k.reshape(B, n_chunks, kv_chunk, Hkv, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, kv_chunk, Hkv, Dv), 1, 0)
    if kv_positions is not None:
        pc = jnp.moveaxis(kv_positions.reshape(1, n_chunks, kv_chunk), 1, 0)
    else:
        pc = jnp.zeros((n_chunks, 1, 1), jnp.int32)  # unused placeholder

    q_pos = jnp.arange(Sq) + q_offset              # absolute q positions

    def body(carry, inp):
        m, l, acc = carry                          # (B,Sq,H), same, (..,Dv)
        kj, vj, pj, j = inp
        if g > 1:
            kj = jnp.repeat(kj, g, axis=2)         # (B,C,H,D) local tile
            vj = jnp.repeat(vj, g, axis=2)
        # scores: (B, Sq, H, C), f32 accumulation
        s = jnp.einsum("bqhd,bchd->bqhc", qs, kj,
                       preferred_element_type=jnp.float32)
        if kv_positions is not None:
            kpos = pj[0]
        else:
            kpos = j * kv_chunk + jnp.arange(kv_chunk)
        mask = jnp.ones((Sq, kv_chunk), bool)
        if causal:
            mask &= q_pos[:, None] >= kpos[None, :]
        static_win = isinstance(window, (int, np.integer))
        if static_win and window > 0:
            mask &= (q_pos[:, None] - kpos[None, :]) < window
        elif not static_win:  # traced per-layer window; 0 means global
            dist_ok = (q_pos[:, None] - kpos[None, :]) < window
            mask &= jnp.where(window > 0, dist_ok, True)
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhc,bchd->bqhd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32,
        )
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Sq, H), jnp.float32)
    a0 = jnp.zeros((B, Sq, H, Dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kc, vc, pc, jnp.arange(n_chunks))
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring (sliding-window) KV caches
# ---------------------------------------------------------------------------

RING_EMPTY_POS = np.int32(2 ** 30)  # huge position -> causally masked


def ring_update(cache_k, cache_v, pos_buf, k_new, v_new, start):
    """Write new tokens into a (B, W, Hkv, D) ring cache.

    Slot p%W holds position p; ``pos_buf`` (W,) tracks which absolute
    position each slot currently holds (RING_EMPTY_POS when empty). Only
    the last W of the incoming tokens are kept — earlier ones can never
    be attended again under a window of W.
    """
    B, Sq = k_new.shape[:2]
    W = cache_k.shape[1]
    if Sq >= W:
        k_new, v_new = k_new[:, -W:], v_new[:, -W:]
        newpos = start + Sq - W + jnp.arange(W)
    else:
        newpos = start + jnp.arange(Sq)
    slots = newpos % W
    ck = cache_k.at[:, slots].set(k_new.astype(cache_k.dtype))
    cv = cache_v.at[:, slots].set(v_new.astype(cache_v.dtype))
    pb = pos_buf.at[slots].set(newpos.astype(pos_buf.dtype))
    return ck, cv, pb


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def gqa_init(key, d: int, n_heads: int, n_kv: int, head_dim: int,
             *, dtype=DEFAULT_DTYPE) -> dict:
    ks = jax.random.split(key, 4)
    return {
        "w_q": init_linear(ks[0], d, n_heads * head_dim, dtype=dtype),
        "w_k": init_linear(ks[1], d, n_kv * head_dim, dtype=dtype),
        "w_v": init_linear(ks[2], d, n_kv * head_dim, dtype=dtype),
        "w_o": init_linear(ks[3], n_heads * head_dim, d, dtype=dtype),
    }


def gqa_apply(
    p: dict, x: jnp.ndarray, *,
    n_heads: int, n_kv: int, head_dim: int,
    positions: jnp.ndarray,          # (B, Sq) absolute positions
    rope_theta: float = 1e4,
    causal: bool = True,
    window: int = 0,
    cache: dict | None = None,       # {"k": (B,Smax,Hkv,D), "v": ..., "len": int}
    kv_seq: jnp.ndarray | None = None,  # cross-attention source (B,Skv,d)
    kv_chunk: int = 1024,
    ring: bool = False,              # cache is a (B,W,...) ring + "pos" buffer
) -> tuple[jnp.ndarray, dict | None]:
    B, Sq, d = x.shape
    q = (x @ p["w_q"]).reshape(B, Sq, n_heads, head_dim)
    src = x if kv_seq is None else kv_seq
    k = (src @ p["w_k"]).reshape(B, src.shape[1], n_kv, head_dim)
    v = (src @ p["w_v"]).reshape(B, src.shape[1], n_kv, head_dim)

    if kv_seq is None:  # self-attention: rotary on q and new k
        q = rope(q, positions, theta=rope_theta)
        k = rope(k, positions, theta=rope_theta)

    new_cache = None
    kv_positions = None
    if cache is not None and ring:
        start = cache["len"]
        ck, cv, pb = ring_update(cache["k"], cache["v"], cache["pos"],
                                 k, v, start)
        new_cache = {"k": ck, "v": cv, "pos": pb}
        if Sq == 1:  # decode: attend the ring with tracked positions
            k, v, kv_positions = ck, cv, pb
        # prefill (Sq>1) from an empty ring: attend the in-flight k/v —
        # the windowed causal mask makes this exact (see ring_update doc)
    elif cache is not None:
        # linear cache: append the Sq new entries at cache["len"]
        start = cache["len"]
        ck = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0)
        )
        cv = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0)
        )
        k, v = ck, cv
        new_cache = {"k": ck, "v": cv, "len": start + Sq}

    out = chunked_attention(
        q, k, v,
        causal=causal and kv_seq is None,
        q_offset=(positions[0, 0] if cache is not None else 0),
        window=window,
        kv_chunk=kv_chunk,
        kv_positions=kv_positions,
    )
    return out.reshape(B, Sq, n_heads * head_dim) @ p["w_o"], new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention)
# ---------------------------------------------------------------------------


def mla_init(key, d: int, n_heads: int, mla, *, dtype=DEFAULT_DTYPE) -> dict:
    ks = jax.random.split(key, 8)
    dq = mla.qk_nope_head_dim + mla.qk_rope_head_dim
    p = {
        # kv compression + decompression
        "w_dkv": init_linear(ks[0], d, mla.kv_lora_rank + mla.qk_rope_head_dim,
                             dtype=dtype),
        "w_uk": init_linear(ks[1], mla.kv_lora_rank,
                            n_heads * mla.qk_nope_head_dim, dtype=dtype),
        "w_uv": init_linear(ks[2], mla.kv_lora_rank,
                            n_heads * mla.v_head_dim, dtype=dtype),
        "w_o": init_linear(ks[3], n_heads * mla.v_head_dim, d, dtype=dtype),
        # kv_a_layernorm: RMSNorm over the latent before it is cached
        "kv_norm": jnp.zeros((mla.kv_lora_rank,), jnp.float32),
    }
    if mla.q_lora_rank:
        p["w_dq"] = init_linear(ks[4], d, mla.q_lora_rank, dtype=dtype)
        p["w_uq"] = init_linear(ks[5], mla.q_lora_rank, n_heads * dq, dtype=dtype)
    else:
        p["w_q"] = init_linear(ks[6], d, n_heads * dq, dtype=dtype)
    return p


def mla_apply(
    p: dict, x: jnp.ndarray, *, n_heads: int, mla,
    positions: jnp.ndarray, rope_theta: float = 1e4,
    cache: dict | None = None,       # {"ckv": (B,Smax,c), "krope": (B,Smax,r), "len"}
    kv_chunk: int = 1024,
    absorbed_decode: bool = True,
) -> tuple[jnp.ndarray, dict | None]:
    """MLA attention. Cache stores compressed c_kv + shared rope key only.

    The decode step's parts run in named scopes that the device trace
    reads back: ``repro.mla.project`` (projections and the absorption),
    ``repro.mla.append`` (the cache row writes) and ``repro.mla.scan``
    (scores, online softmax and the combine over the cache)."""
    B, Sq, d = x.shape
    H = n_heads
    dn, dr, dv, c = (mla.qk_nope_head_dim, mla.qk_rope_head_dim,
                     mla.v_head_dim, mla.kv_lora_rank)

    with jax.named_scope("repro.mla.project"):
        # --- queries
        if mla.q_lora_rank:
            q = (x @ p["w_dq"]) @ p["w_uq"]
        else:
            q = x @ p["w_q"]
        q = q.reshape(B, Sq, H, dn + dr)
        q_nope, q_rope = q[..., :dn], q[..., dn:]
        q_rope = rope(q_rope, positions, theta=rope_theta)

        # --- compressed kv for the new tokens
        dkv = x @ p["w_dkv"]                          # (B,Sq,c+dr)
        ckv_new = rms_norm(dkv[..., :c], p["kv_norm"], eps=KV_NORM_EPS)
        krope_new = rope(dkv[..., None, c:], positions,
                         theta=rope_theta)[..., 0, :]

    if cache is not None:
        start = cache["len"]
        with jax.named_scope("repro.mla.append"):
            ckv = jax.lax.dynamic_update_slice(
                cache["ckv"], ckv_new.astype(cache["ckv"].dtype),
                (0, start, 0))
            krope = jax.lax.dynamic_update_slice(
                cache["krope"], krope_new.astype(cache["krope"].dtype),
                (0, start, 0))
        new_cache = {"ckv": ckv, "krope": krope, "len": start + Sq}
        if absorbed_decode:
            out = _mla_absorbed(p, q_nope, q_rope, ckv, krope, H=H, mla=mla,
                                q_offset=start, kv_chunk=kv_chunk)
            with jax.named_scope("repro.mla.project"):
                return out.reshape(B, Sq, H * dv) @ p["w_o"], new_cache
        ckv_all, krope_all, q_off = ckv, krope, start
    else:
        new_cache = None
        ckv_all, krope_all, q_off = ckv_new, krope_new, 0

    # --- decompressed (train / prefill) path
    Sk = ckv_all.shape[1]
    k_nope = (ckv_all @ p["w_uk"]).reshape(B, Sk, H, dn)
    vfull = (ckv_all @ p["w_uv"]).reshape(B, Sk, H, dv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_all[:, :, None, :], (B, Sk, H, dr))],
        axis=-1,
    )
    qq = jnp.concatenate([q_nope, q_rope], axis=-1)
    out = chunked_attention(
        qq, k, vfull, causal=True, q_offset=q_off, kv_chunk=kv_chunk,
        scale=1.0 / np.sqrt(dn + dr),
    )
    return out.reshape(B, Sq, H * dv) @ p["w_o"], new_cache


def _mla_absorbed(p, q_nope, q_rope, ckv, krope, *, H, mla, q_offset, kv_chunk):
    """Absorbed decode: score against the compressed cache directly.

    q_c = q_nope @ W_uk (per head) -> (B,Sq,H,c); scores = q_c . ckv +
    q_rope . krope (two einsums — never concatenated, so a c-sharded cache
    stays sharded); out_c = attn @ ckv -> decompress via W_uv once.
    """
    B, Sq, _, dn = q_nope.shape
    c, dr, dv = mla.kv_lora_rank, mla.qk_rope_head_dim, mla.v_head_dim
    Sk = ckv.shape[1]
    scale = np.float32(1.0 / np.sqrt(dn + dr))
    with jax.named_scope("repro.mla.project"):
        w_uk = p["w_uk"].reshape(c, H, dn)
        q_c = jnp.einsum("bqhd,chd->bqhc", q_nope.astype(jnp.float32),
                         w_uk.astype(jnp.float32)) * scale
        q_r = q_rope.astype(jnp.float32) * scale
        q_cb = q_c.astype(ckv.dtype)
        q_rb = q_r.astype(krope.dtype)

    kv_chunk = min(kv_chunk, Sk)
    while Sk % kv_chunk:
        kv_chunk -= 1
    n_chunks = Sk // kv_chunk
    q_pos = jnp.arange(Sq) + q_offset

    def body(carry, inp):
        m, l, acc = carry
        cj, rj, j = inp                          # (B,C,c), (B,C,dr)
        s = (jnp.einsum("bqhc,bkc->bqhk", q_cb, cj,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bqhr,bkr->bqhk", q_rb, rj,
                          preferred_element_type=jnp.float32))
        kpos = j * kv_chunk + jnp.arange(kv_chunk)
        mask = q_pos[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, :, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        pch = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + pch.sum(-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bqhk,bkc->bqhc", pch.astype(cj.dtype), cj,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    with jax.named_scope("repro.mla.scan"):
        cc = jnp.moveaxis(ckv.reshape(B, n_chunks, kv_chunk, c), 1, 0)
        rc = jnp.moveaxis(krope.reshape(B, n_chunks, kv_chunk, dr), 1, 0)
        m0 = jnp.full((B, Sq, H), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Sq, H), jnp.float32)
        a0 = jnp.zeros((B, Sq, H, c), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (cc, rc, jnp.arange(n_chunks)))
        out_c = acc / jnp.maximum(l, 1e-30)[..., None]
    with jax.named_scope("repro.mla.project"):
        w_uv = p["w_uv"].reshape(c, H, dv)
        return jnp.einsum("bqhc,chd->bqhd", out_c,
                          w_uv.astype(jnp.float32)).astype(ckv.dtype)
