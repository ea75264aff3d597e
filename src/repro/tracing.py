"""Host spans: what the program was doing, when, and inside what.

A span is a named interval on ``time.perf_counter``'s clock.  It records the
span that was open around it on the same thread (its parent), the thread,
counts known where the work happened (``bytes``, ``leaves``, ``rid`` ...) as
attributes, and the exception that ended it, if one did::

    from repro import tracing

    with tracing.span("store.compress", bytes=len(data)) as s:
        payload = compress(data)
        s.set(bytes_out=len(payload))

Closed spans go to one bounded in-memory buffer (the newest ``CAPACITY``
are kept); ``spans()`` returns them, oldest first by closing time.  While
JAX is loaded an open span is also a ``jax.profiler.TraceAnnotation`` of its
name, so a profiler trace shows it on the host plane, and every backend
compile or compilation-cache load is recorded as a ``jax.compile`` span
(``fun_name``, ``cached``) under the span open on the compiling thread.

Standard library only: this module never imports JAX, so the lake's core
can use it on hosts that have none.
"""

from __future__ import annotations

import collections
import functools
import itertools
import sys
import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional

#: closed spans kept in memory; older ones are dropped
CAPACITY = 65536
#: name of the spans recorded for JAX's compiles
COMPILE = "jax.compile"

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


class Span(NamedTuple):
    id: int
    parent: Optional[int]       # id of the enclosing span on the same thread
    name: str
    t0: float                   # time.perf_counter() seconds
    t1: float
    thread: int                 # threading.get_ident() of the recording thread
    attrs: Dict[str, Any]
    error: Optional[str]        # type name of the exception that ended it


# appending to a deque and copying it are single operations under the
# interpreter lock, so spans are kept without a lock of their own
_buffer: "collections.deque[Span]" = collections.deque(maxlen=CAPACITY)
_hook_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_annotation = None  # jax.profiler.TraceAnnotation, once JAX is loaded


def _stack() -> List[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _on_jax_event(event: str, duration: float, **kw) -> None:
    """JAX's monitoring hook: a cache load is noted, and the backend compile
    that wraps it (fired last, on the same thread) becomes a span."""
    if event == _CACHE_LOAD:
        _local.cache_hit = True
    elif event == _BACKEND_COMPILE:
        t1 = time.perf_counter()
        stack = _stack()
        cached = getattr(_local, "cache_hit", False)
        _local.cache_hit = False
        _buffer.append(Span(
            next(_ids), stack[-1] if stack else None, COMPILE,
            t1 - duration, t1, threading.get_ident(),
            {"fun_name": kw.get("fun_name", "?"), "cached": cached}, None))


def _jax_hooks():
    """TraceAnnotation once JAX has been imported (registering the compile
    listener the first time), else None."""
    global _annotation
    jax = sys.modules.get("jax")
    profiler = getattr(jax, "profiler", None)
    monitoring = getattr(jax, "monitoring", None)
    if profiler is None or monitoring is None:  # not loaded, or loading
        return None
    with _hook_lock:
        if _annotation is None:
            monitoring.register_event_duration_secs_listener(_on_jax_event)
            _annotation = profiler.TraceAnnotation
    return _annotation


class _Open:
    """An open span; ``span()`` returns one.  As a decorator, each call of
    the decorated function is a span of its own."""

    __slots__ = ("name", "attrs", "id", "parent", "t0", "_note")

    def __init__(self, name: str, attrs: Dict[str, Any]):
        self.name, self.attrs = name, attrs

    def set(self, **attrs) -> "_Open":
        """Add attributes known only once the work is done."""
        self.attrs.update(attrs)
        return self

    def elapsed(self) -> float:
        """Seconds since the span opened."""
        return time.perf_counter() - self.t0

    def __enter__(self) -> "_Open":
        stack = _stack()
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        annotation = _annotation or _jax_hooks()
        self._note = annotation(self.name) if annotation else None
        if self._note is not None:
            self._note.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = time.perf_counter()
        if self._note is not None:
            self._note.__exit__(exc_type, exc, tb)
        _stack().pop()
        _buffer.append(Span(self.id, self.parent, self.name, self.t0, t1,
                            threading.get_ident(), self.attrs,
                            exc_type.__name__ if exc_type else None))
        return False

    def __call__(self, fn):
        @functools.wraps(fn)
        def traced(*args, **kwargs):
            with _Open(self.name, dict(self.attrs)):
                return fn(*args, **kwargs)
        return traced


def span(name: str, **attrs) -> _Open:
    """A span named ``name``, to be entered with ``with`` (or used as a
    decorator); ``attrs`` are its first attributes."""
    return _Open(name, attrs)


def record(name: str, t0: float, t1: float, **attrs) -> None:
    """Record a span that opened and closed in different calls, from its
    ``time.perf_counter()`` ends; it has no parent."""
    _buffer.append(Span(next(_ids), None, name, t0, t1,
                        threading.get_ident(), attrs, None))


def spans() -> List[Span]:
    """The closed spans still in the buffer, in the order they closed."""
    return list(_buffer)
