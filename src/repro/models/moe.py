"""Mixture-of-Experts layer with expert parallelism (DeepSeek/Kimi family).

Routing: token-choice softmax top-k (DeepSeek-V2 style), renormalized over
the selected experts, with per-expert capacity ``C = T*k/E * cf`` and
deterministic weight-ranked capacity dropping.

Parallelism: experts are sharded over the EP axes (``model``, plus ``pod``
when the multi-pod mesh is up and the expert count divides); within each
device a ``lax.scan`` walks the local experts, each picking its top-C
assigned tokens (static shapes, no sort/a2a — the token set is replicated
over the EP axes because activations are only batch-sharded, so expert
output partial-sums reduce with one ``psum`` per layer). Optional FSDP
shards the expert d_model dim over ``data`` and all-gathers per layer —
ZeRO-3 semantics, required for the 1T-param config to fit HBM.

The same local kernel runs without shard_map for single-device smoke
tests (``par=None``).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from .layers import DEFAULT_DTYPE, init_linear

__all__ = ["moe_init", "moe_apply", "Parallelism"]


@dataclasses.dataclass(frozen=True)
class Parallelism:
    """Mesh + axis assignment threaded through model apply."""

    mesh: object                     # jax.sharding.Mesh
    dp_axes: tuple[str, ...] = ("data",)   # batch axes
    tp_axis: str = "model"
    ep_axes: tuple[str, ...] = ("model",)  # expert-parallel axes
    fsdp_axes: tuple[str, ...] = ()        # param-shard axes (ZeRO-3)
    pod_axis: str | None = None
    head_dim: int = 0                # head-aware K/V projection sharding
    vocab_axis: str | None = "model"  # embeddings shard here even with TP off
    # activations-only batch axes override. Big-model DECODE replicates
    # the (tiny) activations over data so FSDP-sharded weights compute
    # partial products + psum instead of being all-gathered per layer —
    # the dense-path twin of the MoE weight-stationary rule.
    act_batch_axes: tuple[str, ...] | None = None

    @property
    def batch_axes(self) -> tuple[str, ...]:
        if self.pod_axis and self.pod_axis not in self.ep_axes:
            return (self.pod_axis,) + self.dp_axes
        return ((self.pod_axis,) if self.pod_axis else ()) + self.dp_axes

    @property
    def act_axes(self) -> tuple[str, ...]:
        if self.act_batch_axes is not None:
            return self.act_batch_axes
        return self.batch_axes


def moe_init(key, d: int, moe, *, dtype=DEFAULT_DTYPE) -> dict:
    ks = jax.random.split(key, 5)
    E, fe = moe.n_routed, moe.d_ff_expert
    std = 1.0 / np.sqrt(d)

    def experts(k, d_in, d_out):
        return (jax.random.normal(k, (E, d_in, d_out), jnp.float32)
                * (1.0 / np.sqrt(d_in))).astype(dtype)

    p = {
        "router": (jax.random.normal(ks[0], (d, E), jnp.float32) * std
                   ).astype(jnp.float32),  # router kept f32 for stable top-k
        "w_gate_e": experts(ks[1], d, fe),
        "w_in_e": experts(ks[2], d, fe),
        "w_out_e": (jax.random.normal(ks[3], (E, fe, d), jnp.float32)
                    * (1.0 / np.sqrt(fe))).astype(dtype),
    }
    if moe.n_shared:
        fs = moe.n_shared * fe
        kss = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": init_linear(kss[0], d, fs, dtype=dtype),
            "w_in": init_linear(kss[1], d, fs, dtype=dtype),
            "w_out": init_linear(kss[2], fs, d, dtype=dtype),
        }
    return p


def _local_moe(x2d, gates, w_gate, w_in, w_out, *, top_k: int, capacity: int,
               e_offset: jnp.ndarray | int,
               fsdp: tuple[str, ...] = ()):
    """Process this shard's experts for all (replicated) tokens.

    x2d: (T, d); gates: (T, E_global) f32 probabilities. w_gate/w_in are
    (E_local, d_local, fe) and w_out is (E_local, fe, d_local) where
    d_local = d / prod(fsdp) — the weight-stationary layout: instead of
    ZeRO-3 all-gathering O(GB) expert weights per layer, each fsdp peer
    computes partial products on its d-slice and psums the (C, fe) hidden
    activations — orders of magnitude fewer bytes for decode, and ~equal
    for prefill, with no weight-sized temporaries. Returns the partial
    output (T, d_local) — caller psums over EP axes and all-gathers the
    d_local dim over fsdp.
    """
    T, d = x2d.shape
    E_local = w_gate.shape[0]
    d_local = w_gate.shape[1]

    if fsdp:
        # this peer's d-slice of the (replicated-d) token matrix
        idx = 0
        for a in fsdp:
            idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
        x_l = jax.lax.dynamic_slice_in_dim(x2d, idx * d_local, d_local, 1)
    else:
        x_l = x2d

    # top-k over the *global* expert axis (identical on every EP peer)
    topv, topi = jax.lax.top_k(gates, top_k)              # (T, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    def one_expert(acc, inp):
        w_g, w_i, w_o, e_local = inp
        e_id = e_offset + e_local
        # weight of this expert for each token (0 if not selected)
        sel = (topi == e_id)
        w_tok = jnp.where(sel, topv, 0.0).sum(-1)         # (T,)
        cw, ci = jax.lax.top_k(w_tok, capacity)           # deterministic drop
        xc = jnp.take(x_l, ci, axis=0)                    # (C, d_local)
        gate_h = xc @ w_g
        in_h = xc @ w_i
        if fsdp:  # complete the contraction over d before the nonlinearity
            gate_h = jax.lax.psum(gate_h, fsdp)
            in_h = jax.lax.psum(in_h, fsdp)
        h = jax.nn.silu(gate_h) * in_h
        out = (h @ w_o).astype(jnp.float32) * cw[:, None]  # (C, d_local)
        acc = acc.at[ci].add(jnp.where((cw > 0)[:, None], out, 0.0))
        return acc, None

    acc0 = jnp.zeros((T, d_local), jnp.float32)
    acc, _ = jax.lax.scan(
        one_expert, acc0,
        (w_gate, w_in, w_out, jnp.arange(E_local)),
    )
    return acc


def moe_apply(p: dict, x: jnp.ndarray, moe, *, par: Parallelism | None,
              act: str = "silu") -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (output, aux_loss). x: (B, S, d)."""
    B, S, d = x.shape
    E, k = moe.n_routed, moe.top_k
    x2d = x.reshape(B * S, d)
    gates = jax.nn.softmax((x2d.astype(jnp.float32) @ p["router"]), axis=-1)

    # Switch-style load-balance aux loss (fraction * probability per expert)
    topv, topi = jax.lax.top_k(gates, k)
    load = jnp.mean(
        jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(1), axis=0
    )
    imp = jnp.mean(gates, axis=0)
    aux = E * jnp.sum(load * imp) / k

    if par is None:
        capacity = min(B * S, max(1, int(B * S * k / E * moe.capacity_factor)))
        out = _local_moe(x2d, gates, p["w_gate_e"], p["w_in_e"], p["w_out_e"],
                         top_k=k, capacity=capacity, e_offset=0)
    else:
        ep = par.ep_axes
        ep_size = int(np.prod([par.mesh.shape[a] for a in ep]))
        if E % ep_size != 0:
            raise ValueError(f"{E} experts not divisible by EP={ep_size}")
        # tokens are replicated over EP axes (batch only shards dp axes);
        # keep only batch axes that divide the token count (B=1 decode
        # degrades to fully-replicated tokens)
        batch_spec: tuple[str, ...] = ()
        size = 1
        for a in par.act_axes:
            if a in ep:
                continue
            nxt = size * par.mesh.shape[a]
            if (B * S) % nxt == 0:
                batch_spec += (a,)
                size = nxt
        t_local = B * S // size
        capacity = min(t_local, max(1, int(t_local * k / E * moe.capacity_factor)))
        fsdp = tuple(a for a in par.fsdp_axes if a not in ep)

        xs = P(batch_spec if batch_spec else None, None)
        ws = P(ep, fsdp if fsdp else None, None)
        wos = P(ep, None, fsdp if fsdp else None)

        def shard_fn(x2d_l, gates_l, w_g, w_i, w_o):
            e_local = w_g.shape[0]
            e_off = _ep_offset(ep, e_local)
            out = _local_moe(x2d_l, gates_l, w_g, w_i, w_o,
                             top_k=k, capacity=capacity, e_offset=e_off,
                             fsdp=fsdp)
            for a in ep:
                out = jax.lax.psum(out, a)   # (T, d_local) partial-sum
            if fsdp:
                out = _allgather(out, fsdp, axis=1)  # (T, d)
            return out

        out = jax.shard_map(
            shard_fn, mesh=par.mesh,
            in_specs=(xs, xs, ws, ws, wos),
            out_specs=xs,
            check_vma=False,
        )(x2d, gates, p["w_gate_e"], p["w_in_e"], p["w_out_e"])

    y = out.astype(x.dtype).reshape(B, S, d)

    if "shared" in p:
        sp = p["shared"]
        h = jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_in"])
        y = y + h @ sp["w_out"]
    return y, aux


def _ep_offset(ep_axes: tuple[str, ...], e_local: int):
    idx = 0
    for a in ep_axes:
        idx = idx * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return idx * e_local


def _allgather(w, axes: tuple[str, ...], *, axis: int):
    for a in reversed(axes):
        w = jax.lax.all_gather(w, a, axis=axis, tiled=True)
    return w
