"""In-memory spans at the program's layer boundaries.

``span(name, **attrs)`` is a context manager.  With tracing off (the
default) it returns one shared no-op context: no clock read, no record,
no lock.  Between ``start()`` and ``stop()`` each span keeps a
:class:`Span` record (``time.perf_counter_ns`` bounds, its id, its
parent's id from a per-thread stack, the request or batch id it inherits
from that parent, and ``attrs``) and enters
``jax.profiler.TraceAnnotation("predtrace." + name)``, so a profiler
trace shows it beside the device ops on the same clock.

    trace.start()
    ...                      # serve traffic
    spans = trace.stop()     # the window's records
    trace.self_ns(spans)     # {span id: duration less its children}

The buffer holds at most ``cap`` records; ``dropped()`` counts the rest.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

PREFIX = "predtrace."


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]
    req: Optional[int]
    attrs: dict


class _Off:
    """The shared context every span gets while tracing is off."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        pass


_OFF = _Off()
_on = False
_cap = 0
_records: List[Span] = []
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count(1)


class _Open(threading.local):
    """This thread's open spans, innermost last."""

    def __init__(self):
        self.spans = []


_open = _Open()


class _Live:
    __slots__ = ("name", "req", "attrs", "id", "parent", "start", "_ann")

    def __init__(self, name, req, attrs):
        from jax.profiler import TraceAnnotation

        top = _open.spans[-1] if _open.spans else None
        self.name, self.attrs, self.id = name, attrs, next(_ids)
        self.parent = top.id if top else None
        self.req = req if req is not None or top is None else top.req
        self._ann = TraceAnnotation(PREFIX + name)

    def __enter__(self):
        _open.spans.append(self)
        self._ann.__enter__()
        self.start = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        _open.spans.pop()
        record(self.name, self.start, end, self.req, self.id, self.parent,
               **self.attrs)
        return False

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)


def span(name: str, req: Optional[int] = None, **attrs):
    """Context for one span; ``req`` is a request or batch id, inherited
    from the enclosing span when not given.  ``.set(**attrs)`` adds attrs
    known only inside the span."""
    if not _on:
        return _OFF
    return _Live(name, req, attrs)


def enabled() -> bool:
    return _on


def note(**attrs) -> None:
    """Add attrs to the innermost open span of this thread."""
    if _on and _open.spans:
        _open.spans[-1].attrs.update(attrs)


def record(name: str, start_ns: int, end_ns: int, req: Optional[int] = None,
           span_id: Optional[int] = None, parent: Optional[int] = None,
           **attrs) -> None:
    """Keep a span measured elsewhere (e.g. from stamps taken on another
    thread).  No profiler annotation."""
    global _dropped
    if not _on:
        return
    rec = Span(name, start_ns, end_ns,
               next(_ids) if span_id is None else span_id,
               parent, req, attrs)
    with _lock:
        if len(_records) < _cap:
            _records.append(rec)
        else:
            _dropped += 1


def start(cap: int = 1 << 19) -> None:
    """Clear the buffer and record up to ``cap`` spans."""
    global _on, _cap, _records, _dropped
    with _lock:
        _records, _dropped, _cap = [], 0, int(cap)
        _on = True


def stop() -> List[Span]:
    """Stop recording and hand back the window's spans."""
    global _on, _records
    with _lock:
        _on = False
        out, _records = _records, []
    return out


def dropped() -> int:
    """Spans the last window could not keep."""
    return _dropped


def self_ns(spans: List[Span]) -> Dict[int, int]:
    """Each span's duration less the part of it its children cover."""
    kids: Dict[int, list] = {}
    for s in spans:
        if s.parent is not None:
            kids.setdefault(s.parent, []).append((s.start_ns, s.end_ns))
    out = {}
    for s in spans:
        covered, edge = 0, s.start_ns
        for a, b in sorted(kids.get(s.id, ())):
            a, b = max(a, edge), min(b, s.end_ns)
            if b > a:
                covered += b - a
                edge = b
        out[s.id] = s.end_ns - s.start_ns - covered
    return out
