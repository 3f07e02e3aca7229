"""Named spans at the program's layer boundaries, off by default.

``span(name, **attrs)`` times a block with ``perf_counter``: the object
it yields always has ``start``, ``end`` and ``seconds`` after exit, so
fields such as ``lower_seconds`` read it whether spans are on or off.

Spans are turned on by a call, ``enable()`` (or a ``recording()``
block), never by a variable of the environment. While they are on, a
span also

- enters ``jax.profiler.TraceAnnotation(name)``, so inside a profiler
  session it lands in the ``.xplane.pb`` on the device trace's clock
  (attributes are left out of the annotation: they ride in the buffer);
- appends ``(span_id, parent_id, name, start_ns, end_ns, attrs)`` to a
  bounded in-memory buffer: ``start_ns`` from ``time.time_ns()``, the
  realtime clock of the profiler's host events (an event's start plus
  the trace's ``profile_start_time``), ``end_ns`` that plus ``seconds``.
  ``drain()`` returns and empties the buffer; records past its bound
  are counted (``dropped()``), not kept.

Whether a span records is decided when it is entered. Callables bound
for a hot loop (``wrap``) decide once, when they are bound: spans must be
on before ``bind()`` for its dispatch spans to appear. Program span
names start with ``repro.`` and are stable.

Device scopes. Code inside an executable is named with
``jax.named_scope("repro....")``; the name rides in each HLO
instruction's ``op_name`` metadata, which a device trace's op events do
not carry. Staging notes every executable it compiles or loads
(``note_executable``, a weak reference and nothing more), and
``device_scopes()`` reads their text on demand: per module, each
instruction's innermost ``repro.*`` scope, the map a trace reader needs
to put each device op under its scope.
"""
from __future__ import annotations

import contextlib
import contextvars
import functools
import itertools
import re
import threading
import time
import weakref
from typing import Callable

import jax

# records the buffer keeps between drains; later ones are only counted
CAPACITY = 1 << 16

_on = False
_lock = threading.Lock()
_buffer: list[tuple] = []
_dropped = 0
_ids = itertools.count(1)
# the innermost open span of this thread (``carry`` hands it to a worker
# thread): the parent of the next span entered
_parent: contextvars.ContextVar = contextvars.ContextVar(
    "repro_span_parent", default=None)


class Span:
    """One timed block; see the module's docstring. ``attrs`` may change
    until the span ends; ``close()`` ends it before the block does."""

    __slots__ = ("name", "attrs", "start", "end", "seconds", "_open")

    def __init__(self, name: str, attrs: dict):
        self.name, self.attrs = name, attrs
        self.start = self.end = self.seconds = None
        self._open = None

    def __enter__(self) -> "Span":
        if _on:
            sid = next(_ids)
            ann = jax.profiler.TraceAnnotation(self.name)
            ann.__enter__()
            self._open = (sid, _parent.get(), _parent.set(sid), ann,
                          time.time_ns())
        self.start = time.perf_counter()
        return self

    def close(self) -> None:
        if self.end is not None:
            return
        self.end = time.perf_counter()
        self.seconds = self.end - self.start
        if self._open is not None:
            sid, parent, token, ann, start_ns = self._open
            # the end on the same clock, by the perf_counter duration:
            # the record's length is ``seconds``, to the nanosecond
            end_ns = start_ns + int(self.seconds * 1e9)
            _parent.reset(token)
            ann.__exit__(None, None, None)
            _record((sid, parent, self.name, start_ns, end_ns, self.attrs))

    def __exit__(self, *exc) -> None:
        self.close()


def span(name: str, **attrs) -> Span:
    return Span(name, attrs)


def _record(rec: tuple) -> None:
    global _dropped
    with _lock:
        if len(_buffer) < CAPACITY:
            _buffer.append(rec)
        else:
            _dropped += 1


def wrap(fn: Callable, name: str, **attrs) -> Callable:
    """``fn`` itself while spans are off; else ``fn`` inside a ``name``
    span per call. Decided once, here."""
    if not _on:
        return fn

    def spanned(*args):
        with Span(name, attrs):
            return fn(*args)

    return spanned


def spanned(name: str) -> Callable:
    """Decorator: each call of the function runs inside a ``name`` span."""
    def deco(fn: Callable) -> Callable:
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with Span(name, {}):
                return fn(*args, **kwargs)
        return inner
    return deco


def carry(fn: Callable) -> Callable:
    """``fn`` for another thread: its spans nest under the span open
    here when ``carry`` is called."""
    parent = _parent.get()
    if not _on or parent is None:
        return fn

    def carried(*args):
        token = _parent.set(parent)
        try:
            return fn(*args)
        finally:
            _parent.reset(token)

    return carried


def enable() -> None:
    """Turn spans on; the buffer keeps at most ``CAPACITY`` records."""
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def drain() -> list[tuple]:
    """The buffered records, oldest first; the buffer and the count of
    dropped records start again from empty."""
    global _buffer, _dropped
    with _lock:
        out, _buffer, _dropped = _buffer, [], 0
    return out


def dropped() -> int:
    """Records refused by the bound since the last ``drain()``."""
    with _lock:
        return _dropped


@contextlib.contextmanager
def recording():
    """Spans on inside the block; yields a list that receives the
    buffer's records when the block ends, then restores the previous
    on/off state."""
    global _on
    was, _on = _on, True
    got: list[tuple] = []
    try:
        yield got
    finally:
        got.extend(drain())
        _on = was


# ---------------------------------------------------------------------------
# device scopes: HLO instruction -> repro.* scope, per executable module
# ---------------------------------------------------------------------------

# [weak reference to an executable, its parsed (module, map) or None]
_executables: list[list] = []
_MODULE = re.compile(r"^HloModule (\S+?),? ")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = .*?op_name=\"([^\"]*)\"")


def note_executable(executable) -> None:
    """Keep a weak reference to a compiled executable for
    ``device_scopes``; costs nothing until that is called."""
    with _lock:
        _executables[:] = [e for e in _executables if e[0]() is not None]
        try:
            _executables.append([weakref.ref(executable), None])
        except TypeError:  # an object that cannot be weakly referenced
            pass


def scope_map(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction: scope})`` from an executable's HLO
    text: the scope is the innermost ``repro.*`` component of the
    instruction's ``op_name``; instructions under none are left out."""
    module, out = "", {}
    for line in hlo_text.splitlines():
        if not module:
            m = _MODULE.match(line)
            if m:
                module = m.group(1)
                continue
        m = _INSTR.match(line)
        if m is None:
            continue
        scopes = [c for c in m.group(2).split("/") if c.startswith("repro.")]
        if scopes:
            out[m.group(1)] = scopes[-1]
    return module, out


def device_scopes() -> dict[str, dict[str, str]]:
    """Per module name, each instruction's ``repro.*`` scope, over every
    live executable noted so far; modules of one name merge."""
    with _lock:
        entries = [(e, e[0]()) for e in _executables]
    out: dict[str, dict[str, str]] = {}
    for entry, exe in entries:
        if exe is None:
            continue
        if entry[1] is None:
            entry[1] = scope_map(exe.as_text())
        module, scopes = entry[1]
        out.setdefault(module, {}).update(scopes)
    return out
