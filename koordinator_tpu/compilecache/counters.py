"""JAX compilation telemetry, scoped: the warm-start pins' source of
truth.

jax.monitoring has no unregister, so ONE pair of process-global
listeners installs idempotently on first use and feeds module-global
tallies; `CompileWatcher` snapshots them around a region and exposes
the deltas. The pin that matters (tests, the warm-cache smoke) is
`cache_misses == 0`: with a persistent cache dir
active, `/jax/compilation_cache/cache_misses` fires exactly when XLA
actually compiled, while the backend_compile duration event fires even
on a persistent-cache HIT (it times compile-OR-retrieve) — so compile
durations measure cost, never prove absence of compilation.
"""

from __future__ import annotations

import collections
import threading
from typing import Dict, Optional

from koordinator_tpu.utils.sync import guard_module

# the event names jax 0.4.x emits (jax/_src/compiler.py,
# jax/_src/compilation_cache.py); pinned by tests/test_compilecache.py
EVENT_CACHE_HIT = "/jax/compilation_cache/cache_hits"
EVENT_CACHE_MISS = "/jax/compilation_cache/cache_misses"
DURATION_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
DURATION_TRACE = "/jax/core/compile/jaxpr_trace_duration"

_lock = threading.Lock()
_counts: collections.Counter = collections.Counter()
_durations: Dict[str, float] = collections.defaultdict(float)
_installed = False
# per-thread compile seconds: JAX reports a compile on the thread that
# asked for it, so a cycle can tell its own compiles from another's
_thread = threading.local()
guard_module(__name__, _counts="_lock", _durations="_lock",
             _installed="_lock")


def _on_event(event: str, **_kw) -> None:
    with _lock:
        _counts[event] += 1


def _on_duration(event: str, duration: float, **_kw) -> None:
    with _lock:
        _counts[event] += 1
        _durations[event] += float(duration)
    if event == DURATION_BACKEND_COMPILE:
        _thread.compile_seconds = thread_compile_seconds() + duration


def install() -> None:
    """Idempotently install the process-global listeners. Safe to call
    any number of times; never installs twice (jax.monitoring keeps
    listeners forever, so a second registration would double-count)."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def snapshot() -> tuple:
    """(counts, duration sums) copies of the global tallies."""
    with _lock:
        return dict(_counts), dict(_durations)


def thread_compile_seconds() -> float:
    """XLA compile-or-retrieve seconds spent on the CALLING thread so
    far. Only compiles after `install()` count, so take a reading
    before the region of interest and subtract."""
    return getattr(_thread, "compile_seconds", 0.0)


class CompileWatcher:
    """Context manager exposing the compilation telemetry deltas of its
    region: `cache_hits` / `cache_misses` (persistent-cache events —
    both 0 when no cache dir is configured), `backend_compiles` and
    `compile_seconds` (compile-or-retrieve invocations and their summed
    wall time), `trace_seconds`. Readable live inside the region and
    frozen after exit."""

    def __init__(self) -> None:
        self._c0: Dict[str, int] = {}
        self._d0: Dict[str, float] = {}
        self._c1: Optional[Dict[str, int]] = None
        self._d1: Optional[Dict[str, float]] = None

    def __enter__(self) -> "CompileWatcher":
        install()
        self._c0, self._d0 = snapshot()
        self._c1 = self._d1 = None
        return self

    def __exit__(self, *_exc) -> None:
        self._c1, self._d1 = snapshot()

    def _count(self, event: str) -> int:
        now = self._c1 if self._c1 is not None else snapshot()[0]
        return now.get(event, 0) - self._c0.get(event, 0)

    def _duration(self, event: str) -> float:
        now = self._d1 if self._d1 is not None else snapshot()[1]
        return now.get(event, 0.0) - self._d0.get(event, 0.0)

    @property
    def cache_hits(self) -> int:
        return self._count(EVENT_CACHE_HIT)

    @property
    def cache_misses(self) -> int:
        return self._count(EVENT_CACHE_MISS)

    @property
    def backend_compiles(self) -> int:
        return self._count(DURATION_BACKEND_COMPILE)

    @property
    def compile_seconds(self) -> float:
        return self._duration(DURATION_BACKEND_COMPILE)

    @property
    def trace_seconds(self) -> float:
        return self._duration(DURATION_TRACE)


def watch() -> CompileWatcher:
    """`with counters.watch() as w:` sugar."""
    return CompileWatcher()
