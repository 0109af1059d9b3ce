"""In-process span recorder: which layer of the served path holds a request.

A :class:`Span` is one timed stretch of one layer's work, on
``time.perf_counter_ns()`` — the clock callers time their requests on
and the clock a profiler trace is aligned to, so spans, request records
and device events compare with no conversion.

* **parent** — a span opened with :meth:`Recorder.span` becomes the
  current span of its thread (a ``contextvars`` variable) for the block,
  so spans opened inside it name it as their parent.  Work that crosses
  threads (admission on the caller's thread, the device stage and host
  tail on the engine's executors) carries ``request_id`` instead, or names
  its parent explicitly.
* **buffer** — finished spans stay in memory in a bounded deque (the
  oldest fall out past ``capacity``); :meth:`Recorder.drain` hands them
  over and clears.
* **off by default** — a span site tests ``RECORDER.on`` and does nothing
  else: no clock read, no allocation.  Per-event counts ride on a span's
  ``attrs``; cumulative counts stay in the ``stats()`` dicts of the
  objects that own them.
"""

from __future__ import annotations

import collections
import contextvars
import dataclasses
import itertools
import threading
import time
from typing import Any, Deque, Dict, List, Optional

__all__ = ["Span", "Recorder", "RECORDER"]


@dataclasses.dataclass
class Span:
    name: str
    span_id: int
    parent_id: Optional[int]
    request_id: Optional[int]
    thread: str
    start_ns: int
    end_ns: int = 0
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)


_CURRENT: "contextvars.ContextVar[Optional[Span]]" = contextvars.ContextVar(
    "flexvec_span", default=None)


class _Null:
    """What :meth:`Recorder.span` returns while the recorder is off."""

    def __enter__(self) -> None:
        return None

    def __exit__(self, *exc) -> None:
        return None


_NULL = _Null()


class _Scope:
    __slots__ = ("_rec", "_span", "_token")

    def __init__(self, rec: "Recorder", span: Span):
        self._rec, self._span = rec, span

    def __enter__(self) -> Span:
        self._token = _CURRENT.set(self._span)
        return self._span

    def __exit__(self, *exc) -> None:
        _CURRENT.reset(self._token)
        self._rec.close(self._span)


class Recorder:
    """Bounded in-memory span sink; ``on`` switches every span site."""

    def __init__(self, capacity: int = 1 << 18):
        self.on = False
        self._buf: Deque[Span] = collections.deque(maxlen=capacity)
        self._ids = itertools.count(1)

    def open(self, name: str, request_id: Optional[int] = None, *,
             parent: Optional[Span] = None,
             start_ns: Optional[int] = None) -> Span:
        """A started span, NOT made current (it may end on another
        thread); the parent defaults to this thread's current span, and
        the request id to the parent's."""
        par = parent if parent is not None else _CURRENT.get()
        if request_id is None and par is not None:
            request_id = par.request_id
        return Span(name, next(self._ids),
                    None if par is None else par.span_id, request_id,
                    threading.current_thread().name,
                    time.perf_counter_ns() if start_ns is None else start_ns)

    def close(self, span: Span, end_ns: Optional[int] = None) -> None:
        span.end_ns = time.perf_counter_ns() if end_ns is None else end_ns
        self._buf.append(span)  # deque.append is atomic

    def span(self, name: str, request_id: Optional[int] = None, *,
             parent: Optional[Span] = None):
        """``with RECORDER.span(name) as sp:`` — ``sp`` is the open span
        (current for the block), or None while the recorder is off."""
        if not self.on:
            return _NULL
        return _Scope(self, self.open(name, request_id, parent=parent))

    def emit(self, name: str, start_ns: int, end_ns: int,
             request_id: Optional[int] = None, *,
             parent: Optional[Span] = None, **attrs: Any) -> Span:
        """Record a span whose two ends were timed elsewhere."""
        sp = self.open(name, request_id, parent=parent, start_ns=start_ns)
        sp.attrs.update(attrs)
        self.close(sp, end_ns)
        return sp

    def drain(self) -> List[Span]:
        """Every finished span, oldest first; the buffer is left empty."""
        out: List[Span] = []
        pop = self._buf.popleft
        while True:
            try:
                out.append(pop())
            except IndexError:
                return out


#: the process-wide recorder every span site in the served path writes to
RECORDER = Recorder()
