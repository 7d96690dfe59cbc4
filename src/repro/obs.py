"""In-process span recorder for the ingest and query paths.

A span marks the time one layer held the calling thread::

    from repro import obs

    with obs.span("shrink.pyramid"):
        ...

Each finished span is one tuple ``(name, start_ns, end_ns, id, parent,
root)`` on ``time.perf_counter_ns``: ``id`` is the span's own number,
``parent`` the id of the span that was open on the same thread when it
opened (``None`` for a root) and ``root`` the id of the outermost one, so
every span of one flush or one engine call shares its ``root``.

Recording is off by default.  Off, ``span`` reads no clock, allocates
nothing and returns one shared null context, so the sites stay in the hot
paths at the cost of a call.  The caller that wants spans turns the
recorder on with :func:`enable` and collects them with :func:`take`; they
are kept in memory up to ``CAP`` and counted as dropped past it.
"""
from __future__ import annotations

import itertools
import threading
import time

__all__ = ["CAP", "enable", "disable", "span", "take", "self_time"]

CAP = 2_000_000

_now = time.perf_counter_ns
_on = False
_spans: list[tuple] = []
_dropped = 0
_ids = itertools.count()
_local = threading.local()


class _Null:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


class _Span:
    __slots__ = ("name", "id", "parent", "root", "start")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.id = next(_ids)
        if stack:
            self.parent, self.root = stack[-1].id, stack[0].id
        else:
            self.parent, self.root = None, self.id
        stack.append(self)
        self.start = _now()
        return self

    def __exit__(self, *exc):
        end = _now()
        global _dropped
        _local.stack.pop()
        if len(_spans) < CAP:
            _spans.append((self.name, self.start, end, self.id, self.parent, self.root))
        else:
            _dropped += 1
        return False


def span(name: str):
    """A context manager that records one span named ``name`` while the
    recorder is on, and the shared null context while it is off."""
    return _Span(name) if _on else _NULL


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def take() -> tuple[list[tuple], int]:
    """The spans finished since the last ``take`` and the number dropped
    past ``CAP``; both are cleared.  Spans still open are not included."""
    global _spans, _dropped
    out, dropped = _spans, _dropped
    _spans, _dropped = [], 0
    return out, dropped


def self_time(spans, name: str) -> int:
    """Nanoseconds of the spans named ``name`` less what their direct
    children cover.  Children open and close on their parent's thread
    inside it, so they never overlap one another."""
    ids = set()
    total = 0
    for s in spans:
        if s[0] == name:
            ids.add(s[3])
            total += s[2] - s[1]
    return total - sum(s[2] - s[1] for s in spans if s[4] in ids)
