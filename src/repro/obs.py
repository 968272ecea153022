"""Host spans of the program: where the engine's ticks and the planner's
steps spend their time.

``span(name, **attrs)`` times a block of code twice over:

* it enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so a profiler
  trace taken meanwhile holds the span in its host plane, on the clock the
  device trace is aligned to;
* on exit it appends a record ``(id, parent, name, start, end, attrs)`` to
  a bounded ring shared by the whole process.  ``start`` and ``end`` are
  ``time.perf_counter`` seconds; ``parent`` is the id of the span open
  around it on the same thread (None at the top of a thread).

Recording is always on; a span costs a few microseconds.  Counts ride on
span attributes (``engine.prefill`` carries ``bucket`` and
``prompt_len``), so a reader restricts them to any window with
``spans(since, until)``.  Once the ring is full the oldest records go and
``dropped()`` counts them: a reader that needs every span of a window
checks it.

This module imports only ``jax``, ``time``, ``threading`` and
``collections``, so every layer may use it.
"""
from __future__ import annotations

import collections
import threading
import time

import jax

Span = collections.namedtuple("Span", "id parent name start end attrs")

# A whole 51 s run of the benchmark's chat cell (warm-up, ramp and tail
# included) records about 10,000 spans; the ring holds six times that.
CAPACITY = 1 << 16


class _Thread(threading.local):
    """One thread's open spans, and the next id of a block of ids that
    belongs to this thread alone, so that opening a span takes no lock."""

    def __init__(self, ring: "Ring"):
        self.stack: list = []
        self.next_id = ring._claim_ids()


class Ring:
    """A bounded, thread-safe record of finished spans, oldest first."""

    _IDS_PER_THREAD = 1 << 40

    def __init__(self, capacity: int = CAPACITY):
        self._lock = threading.Lock()
        self._records: collections.deque = collections.deque(maxlen=capacity)
        self._threads = 0
        self._dropped = 0
        self._local = _Thread(self)

    def _claim_ids(self) -> int:
        with self._lock:
            self._threads += 1
            return self._threads * self._IDS_PER_THREAD

    def span(self, name: str, **attrs) -> "_Open":
        """A context manager that records ``name`` over the block it wraps."""
        return _Open(self, name, attrs)

    def append(self, record: tuple) -> None:
        """Keep one finished span's fields (a plain tuple: cheaper to make
        than a ``Span``, which readers get)."""
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self._dropped += 1
            self._records.append(record)

    def spans(self, since: float = float("-inf"),
              until: float = float("inf")) -> list[Span]:
        """Finished spans that started in ``[since, until)``, by start."""
        with self._lock:
            records = self._records.copy()
        return sorted((Span._make(r) for r in records
                       if since <= r[3] < until), key=lambda r: r.start)

    def dropped(self) -> int:
        with self._lock:
            return self._dropped

    def reset(self) -> None:
        with self._lock:
            self._records.clear()
            self._dropped = 0


class _Open:
    """One span while it is open (the context manager ``span`` returns)."""
    __slots__ = ("_ring", "_name", "_attrs", "_ann", "_id", "_parent",
                 "_start")

    def __init__(self, ring: Ring, name: str, attrs: dict):
        self._ring = ring
        self._name = name
        self._attrs = attrs
        self._ann = jax.profiler.TraceAnnotation(name, **attrs)

    def set(self, **attrs) -> None:
        """Add attributes known only once the span is open."""
        self._attrs.update(attrs)
        self._ann.set_metadata(**attrs)

    def __enter__(self) -> "_Open":
        local = self._ring._local
        stack = local.stack
        self._parent = stack[-1] if stack else None
        self._id = local.next_id
        local.next_id += 1
        stack.append(self._id)
        self._ann.__enter__()
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        end = time.perf_counter()
        self._ann.__exit__(*exc)
        self._ring._local.stack.pop()
        self._ring.append((self._id, self._parent, self._name, self._start,
                           end, self._attrs))


_RING = Ring()


def span(name: str, **attrs) -> _Open:
    """A context manager that records ``name`` over the block it wraps, in
    the process's ring."""
    return _RING.span(name, **attrs)


def spans(since: float = float("-inf"), until: float = float("inf")) -> list[Span]:
    """The process's finished spans that started in ``[since, until)``."""
    return _RING.spans(since, until)


def dropped() -> int:
    """Records the process's ring has lost to its bound."""
    return _RING.dropped()


def reset() -> None:
    """Empty the process's ring (tests)."""
    _RING.reset()
