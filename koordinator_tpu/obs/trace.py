"""koordtrace span tracer: a bounded, thread-safe ring buffer of
structured span records on `time.monotonic_ns`, each span mirrored
onto the JAX profiler's timeline while it is open.

Design constraints (tests/test_trace.py pins each):
  * bounded memory — a deque ring; overflow drops the OLDEST record
    and counts the drop (surfaced as `scheduler_trace_spans_dropped`),
  * thread-safe — one lock around buffer mutation; the span stack is
    thread-local so concurrent cycles nest independently,
  * zero overhead when disabled — callers hold `tracer = None` and
    route through a shared no-op span (`NOOP_SPAN`), so the dispatch
    hot path allocates NOTHING when tracing is off,
  * exportable — Chrome trace-event JSON (Perfetto-loadable) and
    JSONL, both carrying (cycle, span, parent, t_start, t_end, attrs).

Timestamps are `monotonic_ns` (immune to wall-clock steps); exports
convert to the microseconds Chrome's `ts`/`dur` expect. A wall-clock
anchor is recorded at construction so post-hoc analysis can map
monotonic time back to an absolute epoch.

The mirror: while a span of a live Tracer is open, a
`jax.profiler.TraceAnnotation` of the same name is open on the calling
thread, so a profiler capture holds every span on the host plane, on
the clock of the device ops (outside a capture an annotation costs no
more than a no-op context manager: on a v5e host a mirrored span
measured 4.26 us against 4.20 us). Instant events and pre-timed
`record_span` records stay in the ring only: they have no open
interval to mirror. A service built with `trace=None` opens no span
and no annotation.
"""

from __future__ import annotations

import io
import json
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

from koordinator_tpu.utils.sync import guarded_by


@dataclass(frozen=True)
class SpanRecord:
    """One closed span (or instant event, when t_end == t_start)."""

    cycle: int
    name: str
    parent: Optional[str]
    t_start_ns: int
    t_end_ns: int
    thread_id: int
    attrs: Dict[str, object] = field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return (self.t_end_ns - self.t_start_ns) / 1e9


class _NoopSpan:
    """The disabled-path span: a single shared instance, no state.

    `__enter__` returns None (NOT an attrs dict) so disabled-path
    callers that try to attach attrs fail loudly in tests rather than
    silently building dicts nobody reads.
    """

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    """An open span; context manager. `__enter__` yields the attrs
    dict so the caller can attach attributes before close (recover()
    uses this for its replay-vs-compile split)."""

    __slots__ = ("_tracer", "name", "cycle", "attrs", "_t0", "_mirror")

    def __init__(self, tracer: "Tracer", name: str, cycle: Optional[int],
                 attrs: Optional[dict]):
        self._tracer = tracer
        self.name = name
        self.cycle = cycle
        self.attrs = dict(attrs) if attrs else {}
        self._t0 = 0
        self._mirror = None

    def __enter__(self) -> dict:
        self._t0 = time.monotonic_ns()
        self._tracer._push(self)
        self._mirror = self._tracer.annotation(self.name)
        self._mirror.__enter__()
        return self.attrs

    def __exit__(self, exc_type, exc, tb):
        self._mirror.__exit__(exc_type, exc, tb)
        t1 = time.monotonic_ns()
        if exc_type is not None:
            self.attrs.setdefault("error", exc_type.__name__)
        self._tracer._pop(self, t1)
        return False


@guarded_by(
    _buf="_lock",
    _head="_lock",
    _dropped="_lock",
    # the span stack lives behind a threading.local handle: each
    # thread nests its own cycles without touching the lock
    _tls="confined",
    # wired by the owning service before the first span opens, never
    # rebound after; hook CALLS deliberately run outside the lock
    observer="publish-once",
    on_drop="publish-once",
    capacity="publish-once",
    anchor_monotonic_ns="publish-once",
    anchor_unix_ns="publish-once",
    pid="publish-once",
    annotation="publish-once",
)
class Tracer:
    """Bounded structured span tracer.

    `capacity` bounds the ring; `observer(name, duration_s)` fires on
    every span close (the service wires it to
    `scheduler_cycle_phase_seconds{phase=...}`); `on_drop()` fires per
    overflow-dropped record (wired to `scheduler_trace_spans_dropped`).
    """

    def __init__(self, capacity: int = 65536,
                 observer: Optional[Callable[[str, float], None]] = None,
                 on_drop: Optional[Callable[[], None]] = None):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        self.capacity = int(capacity)
        self._buf: List[SpanRecord] = []
        self._head = 0          # ring start index once full
        self._dropped = 0
        self._lock = threading.Lock()
        self._tls = threading.local()
        # public, mutable: SchedulerService wires its metric hooks into
        # a caller-supplied tracer through these when they are unset
        self.observer = observer
        self.on_drop = on_drop
        # wall-clock anchor: monotonic t and epoch t sampled together
        self.anchor_monotonic_ns = time.monotonic_ns()
        self.anchor_unix_ns = time.time_ns()
        self.pid = os.getpid()
        # the profiler-timeline mirror of every open span (imported
        # here: the obs package stays importable without jax)
        from jax.profiler import TraceAnnotation

        self.annotation = TraceAnnotation

    # --- span lifecycle ---

    def _stack(self) -> List[_Span]:
        st = getattr(self._tls, "stack", None)
        if st is None:
            st = self._tls.stack = []
        return st

    def span(self, name: str, attrs: Optional[dict] = None,
             cycle: Optional[int] = None) -> _Span:
        """Open a span as a context manager; `with tracer.span(n) as a:`
        yields the attrs dict. Nested spans inherit `cycle` from the
        innermost enclosing span on this thread when not given."""
        return _Span(self, name, cycle, attrs)

    def event(self, name: str, attrs: Optional[dict] = None,
              cycle: Optional[int] = None) -> None:
        """Record an instant event (t_end == t_start)."""
        t = time.monotonic_ns()
        st = self._stack()
        parent = st[-1].name if st else None
        if cycle is None and st:
            cycle = st[-1].cycle
        self._append(SpanRecord(
            cycle=-1 if cycle is None else int(cycle), name=name,
            parent=parent, t_start_ns=t, t_end_ns=t,
            thread_id=threading.get_ident(),
            attrs=dict(attrs) if attrs else {}))

    def record_span(self, name: str, t_start_ns: int, t_end_ns: int,
                    attrs: Optional[dict] = None,
                    cycle: Optional[int] = None,
                    parent: Optional[str] = None) -> None:
        """Append a pre-timed span (tools that measure externally
        still land in the same buffer/format)."""
        self._append(SpanRecord(
            cycle=-1 if cycle is None else int(cycle), name=name,
            parent=parent, t_start_ns=int(t_start_ns),
            t_end_ns=int(t_end_ns), thread_id=threading.get_ident(),
            attrs=dict(attrs) if attrs else {}))

    def _push(self, span: _Span) -> None:
        st = self._stack()
        if span.cycle is None and st:
            span.cycle = st[-1].cycle
        st.append(span)

    def _pop(self, span: _Span, t_end_ns: int) -> None:
        st = self._stack()
        # tolerate exception-unwound stacks: pop through to this span
        while st and st[-1] is not span:
            st.pop()
        if st:
            st.pop()
        parent = st[-1].name if st else None
        rec = SpanRecord(
            cycle=-1 if span.cycle is None else int(span.cycle),
            name=span.name, parent=parent, t_start_ns=span._t0,
            t_end_ns=t_end_ns, thread_id=threading.get_ident(),
            attrs=span.attrs)
        self._append(rec)
        if self.observer is not None:
            self.observer(span.name, rec.duration_s)

    def _append(self, rec: SpanRecord) -> None:
        dropped = False
        with self._lock:
            if len(self._buf) < self.capacity:
                self._buf.append(rec)
            else:
                # overwrite the oldest slot; the ring start advances
                self._buf[self._head] = rec
                self._head = (self._head + 1) % self.capacity
                self._dropped += 1
                dropped = True
        if dropped and self.on_drop is not None:
            self.on_drop()

    # --- query / export ---

    @property
    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def records(self) -> List[SpanRecord]:
        """Snapshot of the ring, oldest first."""
        with self._lock:
            return self._buf[self._head:] + self._buf[:self._head]

    def to_chrome(self) -> dict:
        """Chrome trace-event JSON (the object form Perfetto loads)."""
        events = []
        for r in self.records():
            ev = {
                "name": r.name,
                "cat": "koordtrace",
                "ph": "X",
                "ts": r.t_start_ns / 1e3,
                "dur": (r.t_end_ns - r.t_start_ns) / 1e3,
                "pid": self.pid,
                "tid": r.thread_id,
                "args": {"cycle": r.cycle, "parent": r.parent, **r.attrs},
            }
            if r.t_end_ns == r.t_start_ns:
                ev["ph"] = "i"
                ev["s"] = "t"
                del ev["dur"]
            events.append(ev)
        return {
            "traceEvents": events,
            "displayTimeUnit": "ms",
            "otherData": {
                "tracer": "koordtrace",
                "anchor_monotonic_ns": self.anchor_monotonic_ns,
                "anchor_unix_ns": self.anchor_unix_ns,
                "dropped": self.dropped,
            },
        }

    def to_jsonl(self) -> str:
        """One JSON object per record, oldest first."""
        out = io.StringIO()
        for r in self.records():
            out.write(json.dumps({
                "cycle": r.cycle, "span": r.name, "parent": r.parent,
                "t_start_ns": r.t_start_ns, "t_end_ns": r.t_end_ns,
                "thread": r.thread_id, "attrs": r.attrs,
            }, sort_keys=True))
            out.write("\n")
        return out.getvalue()


def jsonl_record(name: str, duration_s: float,
                 attrs: Optional[dict] = None,
                 cycle: int = -1,
                 parent: Optional[str] = None) -> str:
    """A single koordtrace-JSONL line for a synthetic (externally
    timed) span anchored at t=0 — the shared emit path for tools that
    produce per-phase deltas without a live Tracer
    (tools/trace_fullgate.py)."""
    dur_ns = max(0, int(duration_s * 1e9))
    return json.dumps({
        "cycle": cycle, "span": name, "parent": parent,
        "t_start_ns": 0, "t_end_ns": dur_ns, "thread": 0,
        "attrs": dict(attrs) if attrs else {},
    }, sort_keys=True)
