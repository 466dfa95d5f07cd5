"""Host spans of the serving path, kept in memory.

Off by default.  ``span(name)`` then returns one shared no-op context
manager and records nothing.  ``enable()`` turns the recorder on; from
then on each span records its name, ``time.perf_counter_ns()`` at its
start and end, the index of its parent (the span of the same thread
that was open when it began), the request id where there is one, and a
few host-side integers (rows, positions, block counts).  No span reads
a device array or waits for the device: what a span covers is exactly
the host work it wraps.

``snapshot()`` returns what was recorded, with the ``(time.time_ns(),
time.perf_counter_ns())`` pair that ``enable()`` stored, so that a
reader can place the spans on another wall clock, such as the one a
``jax.profiler`` trace is written against:

    from repro.serve import trace
    trace.enable()
    ...serve...
    trace.disable()
    snap = trace.snapshot()
    wall_ns = lambda perf_ns: perf_ns + snap["anchor"][0] - snap["anchor"][1]

Spans are kept up to ``CAP``; later ones are counted in
``snapshot()["dropped"]`` and not kept.
"""

from __future__ import annotations

import threading
import time

CAP = 1 << 18                 # spans kept; later ones are counted as dropped

_on = False                   # the one flag span() checks
_lock = threading.Lock()
_local = threading.local()    # per thread: the stack of open span indices
_spans: list = []             # [name, start, end, parent, rid, attrs]
_dropped = 0
_anchor = (0, 0)


class _NoSpan:
    """The shared context manager of a recorder that is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _NoSpan()


class _Span:
    __slots__ = ("rec", "index")

    def __init__(self, rec: list, index: int):
        self.rec, self.index = rec, index

    def __enter__(self):
        _stack().append(self.index)
        self.rec[1] = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        self.rec[2] = time.perf_counter_ns()
        _stack().pop()
        return False


def _stack() -> list:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _new(name: str, rid, attrs: dict, start: int = 0, end: int = 0,
         nested: bool = True):
    """Append one record; (its index, the record), or None when the cap
    is reached."""
    global _dropped
    stack = _stack() if nested else None
    parent = stack[-1] if stack else -1
    rec = [name, start, end, parent, rid, attrs]
    with _lock:
        if len(_spans) >= CAP:
            _dropped += 1
            return None
        _spans.append(rec)
        return len(_spans) - 1, rec


def span(name: str, rid: int | None = None, **attrs):
    """A context manager that records the host time of its body."""
    if not _on:
        return _NOOP
    new = _new(name, rid, attrs)
    return _NOOP if new is None else _Span(new[1], new[0])


def record(name: str, start_ns: int, end_ns: int, rid: int | None = None,
           **attrs) -> None:
    """Record a span whose ends were stamped earlier (perf_counter ns),
    such as a request's time in the queue; it has no parent."""
    if _on:
        _new(name, rid, attrs, int(start_ns), int(end_ns), nested=False)


def annotate(**attrs) -> None:
    """Add host-side integers to this thread's innermost open span."""
    if _on:
        stack = _stack()
        if stack and stack[-1] < len(_spans):
            _spans[stack[-1]][5].update(attrs)


def annotate_span(span, **attrs) -> None:
    """Add host-side integers to ``span`` (what :func:`span` returned),
    open or closed."""
    if _on and isinstance(span, _Span):
        span.rec[5].update(attrs)


def enabled() -> bool:
    return _on


def enable() -> None:
    """Clear what was recorded and start recording, anchoring
    ``perf_counter_ns`` to the wall clock."""
    global _on, _dropped, _anchor
    with _lock:
        _spans.clear()
        _dropped = 0
        _anchor = (time.time_ns(), time.perf_counter_ns())
        _on = True


def disable() -> None:
    """Stop recording; what was recorded stays for ``snapshot()``."""
    global _on
    _on = False


def snapshot() -> dict:
    """The anchor, the spans recorded so far and the count dropped.
    Each span is a dict: ``name``, ``start`` and ``end`` (perf_counter
    ns; ``end`` 0 while it is open), ``parent`` (an index into the list,
    -1 for none), ``rid`` and ``attrs``."""
    with _lock:
        spans = [{"name": n, "start": s, "end": e, "parent": p, "rid": r,
                  "attrs": dict(a)} for n, s, e, p, r, a in _spans]
        return {"anchor": list(_anchor), "spans": spans,
                "dropped": _dropped}
