"""Nestable trace spans that keep a record, on the profiler's clock.

`span(name, **args)` is the one tracing primitive. On exit it appends
one `Span` record — `(id, parent_id, name, start, end, tick, rid,
args)`, monotonic `perf_counter` times — to a bounded ring, and for as
long as it is open it holds a `jax.profiler.TraceAnnotation` of the
same name, so the span also appears, with no switch, in any profiler
capture (`/profilez`, `train.listeners.ProfilerListener`, a benchmark's
own trace). `mark(name, **args)` appends a zero-length record.

The ring is process-wide by default (`default_spans()`): a reader may
outlive the object that made the spans. `SpanRing.snapshot()` returns
the records with a clock anchor, one `(perf_counter, time_ns)` pair
read together; the profiler stamps its events in `time_ns`'s clock, so
`Snapshot.to_trace_s` puts a span beside the device's operations in a
trace. Disabling is injection, never an `if`: `spans=NULL_SPANS` makes
no record and opens no annotation (`NULL_REGISTRY` / `NULL_RECORDER`).

`tick` and `rid` are the join keys: a child span inherits its parent's,
and the flight recorder's request events (`observability/events.py`)
carry the same `rid`. `annotate(**args)` adds to the innermost open
span's args what is only known at its end.

Callers that pass a `registry` also get the block's wall time in the
`trace_span_seconds{span=...}` histogram under the slash-joined
qualified name of the enclosing stack ("fit" inside "epoch" records as
"epoch/fit"); the serving tick's spans do not pay for it.

The span stack is thread-local: concurrent threads (the serving
engine's background worker, async prefetch producers) nest
independently, and a span's parent is always on its own thread.
"""
from __future__ import annotations

import functools
import itertools
import threading
import time
from collections import deque
from typing import List, NamedTuple, Optional, Tuple

try:
    from jax.profiler import TraceAnnotation as _Annotation
except Exception:        # jax-free callers, stripped builds
    _Annotation = None

_now = time.perf_counter
_tls = threading.local()

_SPAN_HELP = ("Wall time of observability.tracing spans, labeled by "
              "slash-qualified span name")

#: Holds a 51 s serving window with its ramp at about ten records a
#: tick; a full ring drops its oldest record.
DEFAULT_CAPACITY = 1 << 17


class Span(NamedTuple):
    """One closed span (`start < end`) or mark (`start == end`)."""
    id: int
    parent_id: Optional[int]
    name: str
    start: float
    end: float
    tick: Optional[int]
    rid: Optional[int]
    args: dict


class Snapshot(NamedTuple):
    """A ring's records, oldest first, and the clock anchor: `anchor[0]`
    on `perf_counter` is `anchor[1]` nanoseconds on `time.time_ns`."""
    spans: Tuple[Span, ...]
    anchor: Tuple[float, int]

    def to_trace_s(self, t: float) -> float:
        """A `perf_counter` time in seconds of the profiler's clock."""
        return self.anchor[1] * 1e-9 + (t - self.anchor[0])


def clock_anchor() -> Tuple[float, int]:
    """`(perf_counter, time_ns)` of one moment: the tightest of three
    bracketed readings."""
    best = None
    for _ in range(3):
        p0 = _now()
        wall = time.time_ns()
        p1 = _now()
        if best is None or p1 - p0 < best[0]:
            best = (p1 - p0, (p0 + p1) / 2, wall)
    return best[1], best[2]


class SpanRing:
    """Thread-safe bounded ring of `Span` records."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        self.capacity = int(capacity)
        if self.capacity < 1:
            raise ValueError(
                f"span ring capacity must be >= 1, got {capacity}")
        self._ring: deque = deque(maxlen=self.capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)

    def _open(self, name: str, annotated: bool = True):
        """A new span's id and its profiler annotation, entered."""
        annot = None
        if annotated and _Annotation is not None:
            try:
                annot = _Annotation(name)
                annot.__enter__()
            except Exception:    # profiler backends can refuse
                annot = None
        return next(self._ids), annot

    def _close(self, record: Span, annot) -> None:
        if annot is not None:
            try:
                annot.__exit__(None, None, None)
            except Exception:
                pass
        with self._lock:
            self._ring.append(record)

    def snapshot(self) -> Snapshot:
        with self._lock:
            spans = tuple(self._ring)
        return Snapshot(spans, clock_anchor())

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


class NullSpans:
    """Ring that records nothing and opens no annotation: `span()`
    still nests and yields, `snapshot()` is empty."""

    capacity = 0

    def _open(self, name: str, annotated: bool = True):
        return None, None

    def _close(self, record: Span, annot) -> None:
        pass

    def snapshot(self) -> Snapshot:
        return Snapshot((), clock_anchor())

    def __len__(self) -> int:
        return 0


NULL_SPANS = NullSpans()
_default = SpanRing()


def default_spans() -> SpanRing:
    """The process-wide ring (`spans=None` everywhere means this)."""
    return _default


def _stack() -> List["span"]:
    stack = getattr(_tls, "stack", None)
    if stack is None:
        stack = _tls.stack = []
    return stack


def current_span() -> Optional[str]:
    """Qualified name of the innermost active span on this thread."""
    stack = getattr(_tls, "stack", None)
    return "/".join(s.name for s in stack) if stack else None


def annotate(**args) -> None:
    """Add `args` to the innermost open span of this thread (what a
    block only knows at its end). No open span: nothing happens."""
    stack = getattr(_tls, "stack", None)
    if stack:
        stack[-1].args.update(args)


class span:
    """Context manager: `with span("engine.tick", spans=ring, tick=7):`.

    Nestable; yields the slash-qualified name of the enclosing stack.
    `spans=None` records into `default_spans()`, `NULL_SPANS` nowhere.
    `tick=` and `rid=` become the record's join keys (inherited from the
    parent span where not given), every other keyword its `args`. With a
    `registry` the wall time is also observed into
    `trace_span_seconds{span=<qualified name>}`."""

    __slots__ = ("name", "args", "tick", "rid", "_ring", "_registry",
                 "_id", "_parent", "_annot", "_t0", "_qual")

    def __init__(self, name: str, registry=None, spans=None,
                 tick: Optional[int] = None, rid: Optional[int] = None,
                 **args):
        self.name = str(name)
        self.args = args
        self.tick = tick
        self.rid = rid
        self._ring = _default if spans is None else spans
        self._registry = registry

    def __enter__(self) -> str:
        stack = _stack()
        self._parent = None
        if stack:
            parent = stack[-1]
            self._parent = parent._id
            if self.tick is None:
                self.tick = parent.tick
            if self.rid is None:
                self.rid = parent.rid
        stack.append(self)
        self._qual = "/".join(s.name for s in stack)
        self._id, self._annot = self._ring._open(self.name)
        self._t0 = _now()
        return self._qual

    def __exit__(self, *exc) -> bool:
        t1 = _now()
        _stack().pop()
        self._ring._close(
            Span(self._id, self._parent, self.name, self._t0, t1,
                 self.tick, self.rid, self.args), self._annot)
        if self._registry is not None:
            self._registry.histogram(
                "trace_span_seconds", _SPAN_HELP, labelnames=("span",)
            ).labels(self._qual).observe(t1 - self._t0)
        return False


def mark(name: str, spans=None, tick: Optional[int] = None,
         rid: Optional[int] = None, **args) -> None:
    """A zero-length record under the innermost open span, with its
    `tick` and `rid` where none are given. No annotation: the profiler
    has no use for an instant."""
    ring = _default if spans is None else spans
    stack = getattr(_tls, "stack", None)
    parent = stack[-1] if stack else None
    if parent is not None:
        tick = parent.tick if tick is None else tick
        rid = parent.rid if rid is None else rid
    sid, _ = ring._open(name, annotated=False)
    t = _now()
    ring._close(Span(sid, parent._id if parent else None, str(name), t, t,
                     tick, rid, args), None)


def traced(name: Optional[str] = None, registry=None, spans=None):
    """Decorator form of `span` (span name defaults to the function's
    qualified name)."""
    def deco(fn):
        span_name = name or fn.__qualname__

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(span_name, registry=registry, spans=spans):
                return fn(*args, **kwargs)
        return wrapper
    return deco
