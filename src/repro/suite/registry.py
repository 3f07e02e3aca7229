"""The process-wide workload registry.

Registration order is execution order (``benchmarks.run`` iterates
``names()``), so the suite stays deterministic. Re-registering a name
overwrites — module reloads and test fixtures stay idempotent.
"""
from __future__ import annotations

from .workload import Workload

__all__ = ["register", "workload", "workloads", "names", "all_tags",
           "load_builtins"]

_REGISTRY: dict[str, Workload] = {}


def register(w: Workload) -> Workload:
    """Register (or re-register) a workload; returns it for chaining."""
    _REGISTRY[w.name] = w
    return w


def workload(name: str) -> Workload:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"no workload {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def workloads() -> tuple[Workload, ...]:
    return tuple(_REGISTRY.values())


def names() -> tuple[str, ...]:
    return tuple(_REGISTRY)


def all_tags() -> tuple[str, ...]:
    """Every tag used by a registered workload (sorted)."""
    out: set[str] = set()
    for w in _REGISTRY.values():
        out.update(w.tags)
    return tuple(sorted(out))


def load_builtins() -> None:
    """Import the built-in declarative entries (idempotent)."""
    from . import catalog as _builtin  # noqa: F401
    from . import decode as _decode
    from . import derived as _derived
    from . import spatter_io as _spatter

    _derived.register_derived()
    _decode.register_decode()
    _spatter.register_trace()
