"""Program spans: where the serving path spends its host time.

``span(name, **attrs)`` marks one piece of work at a layer boundary of the
serving path (runtime, scheduler, engine).  Each span does two things:

  * it enters ``jax.profiler.TraceAnnotation(name)``, so a profiler trace
    shows it on the host plane, on the same clock as the device's ops;
  * on exit it appends ``(name, start_ns, end_ns, parent, attrs)`` to one
    process-wide ring (``time.perf_counter_ns`` stamps; ``parent`` is the
    name of the span it ran inside, on its thread, or None).

The ring is always on and bounded (``MAXLEN`` records): a long-running
server keeps the newest records and counts the ones it pushed out
(``dropped``).  Nothing is exported; a reader takes ``records()`` in the
process, after the work it wants to read.  Spans of one request share its
``req_id`` attribute.

``record`` files a span whose start and end were measured elsewhere (a
request's walk from admission to its first token spans many steps), with
no annotation of its own."""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import List, Optional, Tuple

import jax

MAXLEN = 2 ** 16

Record = Tuple[str, int, int, Optional[str], dict]

_ring: deque = deque(maxlen=MAXLEN)
_open = threading.local()           # per thread: names of the open spans
_dropped = 0
_now = time.perf_counter_ns


def _append(rec: Record) -> None:
    global _dropped
    if len(_ring) == MAXLEN:
        _dropped += 1
    _ring.append(rec)


class span:
    """Context manager for one span; ``attrs`` may be added to while it is
    open, and ``start_ns`` / ``end_ns`` read after it closed."""

    __slots__ = ("name", "attrs", "parent", "start_ns", "end_ns", "_ann")

    def __init__(self, name: str, **attrs):
        self.name = name
        self.attrs = attrs
        self.end_ns = None

    def __enter__(self) -> "span":
        try:
            stack = _open.names
        except AttributeError:
            stack = _open.names = []
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = ann = jax.profiler.TraceAnnotation(self.name)
        ann.__enter__()
        self.start_ns = _now()
        return self

    def __exit__(self, *exc) -> bool:
        self.end_ns = _now()
        self._ann.__exit__(*exc)
        _open.names.pop()
        _append((self.name, self.start_ns, self.end_ns, self.parent,
                 self.attrs))
        return False


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """File a span measured elsewhere (no parent, no profiler
    annotation)."""
    _append((name, start_ns, end_ns, None, attrs))


def records() -> List[Record]:
    """A snapshot of the ring, oldest first (in the order spans ended)."""
    return list(_ring)


def dropped() -> int:
    """Records pushed out of the full ring since the last ``clear``."""
    return _dropped


def clear() -> None:
    global _dropped
    _ring.clear()
    _dropped = 0
