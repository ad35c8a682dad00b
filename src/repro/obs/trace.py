"""Lightweight span tracing + the one shared wall-clock helper.

``span(name)`` times a stage and records the duration (microseconds)
into the default registry's histogram ``<name>_us``.  Spans nest —
a thread-local stack tracks the active path (``Span.path`` is
``"parent/child"``) — and are exception-safe: the duration records and
the stack pops even when the body raises.  When the registry is
disabled and no profiler session runs, ``span`` returns a shared no-op
singleton: two flag checks, no clock read, zero allocation.

``timeblock(name)`` is the repo's ONE timing idiom, unifying the
hand-rolled ``time.perf_counter()`` blocks the serve/train/bench loops
each grew independently.  Unlike ``span`` it ALWAYS measures (the
loops need wall-clock for QPS whether or not metrics are on) and only
the registry recording is gated.  ``tb.sync(value)`` is the one sync
point: ``jax.block_until_ready`` on any pytree (replacing the
inconsistent ``jax.block_until_ready(out)`` vs
``out.block_until_ready()`` idioms that made cross-site latencies
non-comparable).

    with obs.timeblock("serve.request") as tb:
        out = serve_fn(batch)
        tb.sync(out)                 # device work drains inside the clock
    lat_seconds = tb.seconds         # histogram gets serve.request_us

Both put the program's spans on the profiler's clock: while a profiler
session runs (``jax.profiler.start_trace``) each is also written into
the trace as a ``jax.profiler.TraceAnnotation`` of its name, on the
host plane beside the device's operations.  And each appends
``(name, parent, key, start_ns, end_ns)`` to the span log, a ring of
the last ``SPAN_LOG_SIZE`` spans on ``time.perf_counter_ns``: ``parent``
is the innermost span or named timeblock open on the thread, ``key``
joins the spans of one unit of work (a served micro-batch: the request
count before it), inherited from the parent unless given.  A span
records while the registry is enabled or a profiler session runs; a
named timeblock always records.

Compiles are logged the same way: a ``jax.monitoring`` listener turns
each backend compile into a span-log entry ``jax.compile`` (or
``jax.cache_load`` when the persistent compilation cache supplied the
executable) over ``[end - duration, end]``, and keeps always-on counts
and seconds of each.  ``span_log()`` reads the log and those counters.
"""

from __future__ import annotations

import collections
import threading
import time
from typing import NamedTuple

import jax
from jax import monitoring

from repro.obs import registry as _reg

SPAN_LOG_SIZE = 1 << 16

# the profiler's own "is a session recording" check (TraceMe's)
_tracing = jax.profiler.TraceAnnotation.is_enabled

_tls = threading.local()
_log: collections.deque = collections.deque(maxlen=SPAN_LOG_SIZE)


def _stack() -> list:
    s = getattr(_tls, "stack", None)
    if s is None:
        s = _tls.stack = []
    return s


def _sync(value):
    """Drain device work referenced by ``value`` (any pytree; None is a
    no-op) so the enclosing clock measures finished work, not dispatch.
    """
    if value is not None:
        jax.block_until_ready(value)
    return value


class _Frame:
    """Shared open/close of a recorded span or named timeblock: the
    thread's stack (parent, key), the profiler annotation and the
    span-log entry."""

    __slots__ = ()

    def _open(self) -> None:
        s = _stack()
        parent = s[-1] if s else None
        self.parent = parent.name if parent is not None else None
        if self.key is None and parent is not None:
            self.key = parent.key
        s.append(self)
        self._ann = None
        if _tracing():
            self._ann = jax.profiler.TraceAnnotation(self.name)
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()

    def _close(self) -> int:
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        s = _stack()
        if s and s[-1] is self:
            s.pop()
        elif self in s:              # closed out of order (start/stop)
            s.remove(self)
        _log.append((self.name, self.parent, self.key, self._t0, t1))
        return t1 - self._t0


class Span(_Frame):
    """Timed stage: records ``<name>_us`` on exit (even on exception)."""

    __slots__ = ("name", "path", "seconds", "key", "parent", "_t0",
                 "_ann")

    def __init__(self, name: str, key: int | None = None):
        self.name = name
        self.path = name
        self.seconds = 0.0
        self.key = key

    def __enter__(self) -> "Span":
        self._open()
        self.path = "/".join(f.name for f in _stack())
        return self

    def sync(self, value):
        return _sync(value)

    def __exit__(self, exc_type, exc, tb) -> bool:
        self.seconds = self._close() * 1e-9
        reg = _reg.get_registry()
        if reg.enabled:
            reg.observe(self.name + "_us", self.seconds * 1e6)
        return False


class _NullSpan:
    """Untraced, disabled-mode singleton: no clock, no stack, no
    recording."""

    __slots__ = ()
    name = path = ""
    seconds = 0.0

    def __enter__(self):
        return self

    @staticmethod
    def sync(value):
        return value

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False


_NULL_SPAN = _NullSpan()


def span(name: str, key: int | None = None):
    """Context manager timing one stage into histogram ``<name>_us`` and
    the span log.  Two flag checks and nothing else when the registry
    is disabled and no profiler session runs."""
    if not (_reg.get_registry().enabled or _tracing()):
        return _NULL_SPAN
    return Span(name, key)


def current_path() -> str:
    """The active span path ("a/b/c"), "" outside any span."""
    return "/".join(f.name for f in _stack())


class Timeblock(_Frame):
    """Always-on wall-clock (``seconds`` after exit) and, when named,
    span-log entry; registry recording of ``<name>_us`` only when
    metrics are enabled."""

    __slots__ = ("name", "seconds", "key", "parent", "_t0", "_ann")

    def __init__(self, name: str | None = None):
        self.name = name
        self.seconds = 0.0
        self.key = None

    def __enter__(self) -> "Timeblock":
        if self.name is None:
            self._t0 = time.perf_counter_ns()
        else:
            self._open()
        return self

    def sync(self, value):
        return _sync(value)

    def __exit__(self, exc_type, exc, tb) -> bool:
        if self.name is None:
            self.seconds = (time.perf_counter_ns() - self._t0) * 1e-9
            return False
        self.seconds = self._close() * 1e-9
        reg = _reg.get_registry()
        if reg.enabled:
            reg.observe(self.name + "_us", self.seconds * 1e6)
        return False

    # explicit protocol for regions that don't nest as a `with` block
    # (e.g. pipeline stages threaded through straight-line code)
    def start(self) -> "Timeblock":
        return self.__enter__()

    def stop(self) -> float:
        self.__exit__(None, None, None)
        return self.seconds


def timeblock(name: str | None = None) -> Timeblock:
    return Timeblock(name)


# -- compiles -----------------------------------------------------------

COMPILE = "jax.compile"
CACHE_LOAD = "jax.cache_load"
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_compiles = {COMPILE: [0, 0.0], CACHE_LOAD: [0, 0.0]}
_compiles_lock = threading.Lock()


def _on_duration(event: str, duration: float, **kwargs) -> None:
    """``jax.monitoring`` listener.  A persistent-cache hit reports its
    retrieval first, inside the backend-compile event that follows and
    spans it, so that event is a cache load, not a compile."""
    if event == _CACHE_RETRIEVAL:
        _tls.cache_hit = True
        return
    if event != _BACKEND_COMPILE:
        return
    kind = CACHE_LOAD if getattr(_tls, "cache_hit", False) else COMPILE
    _tls.cache_hit = False
    t1 = time.perf_counter_ns()
    s = _stack()
    parent = s[-1] if s else None
    _log.append((kind, parent.name if parent is not None else None,
                 parent.key if parent is not None else None,
                 t1 - int(duration * 1e9), t1))
    with _compiles_lock:
        c = _compiles[kind]
        c[0] += 1
        c[1] += duration


monitoring.register_event_duration_secs_listener(_on_duration)


class SpanLog(NamedTuple):
    spans: list       # (name, parent, key, start_ns, end_ns), oldest first
    compiles: dict    # COMPILE / CACHE_LOAD -> (count, seconds)


def span_log() -> SpanLog:
    """The span log (``time.perf_counter_ns``) and the compile counters
    since the process started."""
    with _compiles_lock:
        compiles = {k: (c[0], c[1]) for k, c in _compiles.items()}
    return SpanLog(list(_log), compiles)
