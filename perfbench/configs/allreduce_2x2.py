"""Plain reference of the all-reduce: the sum over the shards.

``x`` is the global ``(k * n,)`` input, one ``n``-element shard per
device; every device's copy of the result must be the shards' sum.
"""
from __future__ import annotations


def reference(x: dict, n, cfg: dict, rnd) -> dict:
    """``rnd`` rounds every input and every result to the precision the
    reference computes in."""
    k = int(cfg["devices"])
    shards = rnd(x["x"]).reshape(k, -1)
    total, mag = shards[0], abs(shards[0])
    for s in range(1, k):
        total = rnd(total + shards[s])
        mag = mag + abs(shards[s])
    return {"out": (total, mag)}


def traffic_bytes(cfg: dict, n: int) -> dict:
    """Ring-accounted wire bytes of one call, over all ``k`` devices:
    reduce-scatter plus all-gather move ``2 (k-1)/k`` of the ``n``-element
    f32 buffer per device."""
    k = int(cfg["devices"])
    return {"ici": 2 * (k - 1) * int(n) * 4}
