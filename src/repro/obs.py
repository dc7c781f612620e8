"""Spans and counters of the served path.

One recorder, always on:

* ``span(name, **attrs)`` times a block on ``time.monotonic_ns()``.
  Each span gets an id and, as its parent, the id of the span open
  around it on the same thread (0 at the top).  Attributes are plain
  ints and strings; ``sp.set(**attrs)`` adds some inside the block.
  While a profile is being taken the span also enters
  ``jax.profiler.TraceAnnotation(name, **attrs)`` with the attributes
  known at entry, so it lands on the profile's ``/host:CPU`` plane
  beside the device ops; with no profiler running that costs one check.
* ``record(name, t0_ns, t1_ns, **attrs)`` adds a span that began in an
  earlier callback (the ring only: the profiler takes no past spans).
* Spans go into a bounded ring (``spans()``), oldest dropped first.
  ``complete_since(t_ns)`` says whether every span that ended at or
  after ``t_ns`` is still there, so a reader of a window can tell a
  dropped start from an empty one.
* ``count(name, n)`` adds to process-wide integer counters;
  ``counters()`` is a snapshot.  ``compiles`` counts the backend
  compiles of the process (a ``jax.monitoring`` listener).

Program spans are named ``rapid.<what>``.
"""
from __future__ import annotations

import collections
import itertools
import threading
import time
from typing import Dict, List, NamedTuple

import jax
from jax.profiler import TraceAnnotation

RING = 1 << 16            # spans kept: a 51 s window of sc2-chat is ~15k
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_ring: "collections.deque[Rec]" = collections.deque(maxlen=RING)
_dropped_until = -1       # latest end of a span the ring dropped
_ids = itertools.count(1)
# the served path's counters, present from the start
COUNTERS = ("steps", "compiles", "prefill_tokens_valid",
            "prefill_tokens_padded", "decode_slot_steps_live",
            "decode_slot_steps_free")
_counters: Dict[str, int] = dict.fromkeys(COUNTERS, 0)


class Rec(NamedTuple):
    """A kept span: ``start`` and ``end`` in monotonic ns; ``parent`` is
    the ``sid`` of the span around it (0: none).  Plain values only, so
    the garbage collector stops scanning a full ring."""
    name: str
    start: int
    end: int
    sid: int
    parent: int
    attrs: dict


class Span:
    """The context manager ``span()`` returns; ``set(**attrs)`` adds
    attributes to the record it keeps on exit."""
    __slots__ = ("name", "start", "end", "sid", "parent", "attrs", "_ann")

    def __init__(self, name: str, attrs: dict):
        self.name = name
        self.attrs = attrs

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Span":
        stack = _local.stack
        self.parent = stack[-1].sid if stack else 0
        self.sid = next(_ids)
        stack.append(self)
        if _profiling():
            self._ann = TraceAnnotation(self.name, **self.attrs)
            self._ann.__enter__()
        else:
            self._ann = None
        self.start = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.end = _now()
        if self._ann is not None:
            self._ann.__exit__(*exc)
            self._ann = None
        _local.stack.pop()
        _keep(Rec(self.name, self.start, self.end, self.sid, self.parent,
                  self.attrs))


class _Local(threading.local):
    def __init__(self):
        self.stack: List[Span] = []


_local = _Local()
_now = time.monotonic_ns
_profiling = TraceAnnotation.is_enabled


def _keep(rec: Rec) -> None:
    global _dropped_until
    if len(_ring) == RING:
        _dropped_until = max(_dropped_until, _ring[0].end)
    _ring.append(rec)


def span(name: str, **attrs) -> Span:
    """``with span("rapid.prefill", rid=3, worker=0) as sp: ...``"""
    return Span(name, attrs)


def record(name: str, t0_ns: int, t1_ns: int, **attrs) -> Rec:
    """Keep a span of ``[t0_ns, t1_ns)`` whose start came earlier; it
    has no parent."""
    rec = Rec(name, t0_ns, t1_ns, next(_ids), 0, attrs)
    _keep(rec)
    return rec


def spans() -> List[Rec]:
    """The ring, oldest first."""
    return list(_ring)


def complete_since(t_ns: int) -> bool:
    """True when no span that ended at or after ``t_ns`` was dropped."""
    return _dropped_until < t_ns


def count(name: str, n: int = 1) -> None:
    _counters[name] = _counters.get(name, 0) + n


def counter(name: str) -> int:
    return _counters.get(name, 0)


def counters() -> Dict[str, int]:
    return dict(_counters)


def reset() -> None:
    """Empty the ring and zero the counters."""
    global _dropped_until
    _ring.clear()
    _counters.clear()
    _counters.update(dict.fromkeys(COUNTERS, 0))
    _dropped_until = -1


def _on_duration(event: str, duration: float, **kw) -> None:
    if event == _COMPILE_EVENT:
        count("compiles")


jax.monitoring.register_event_duration_secs_listener(_on_duration)
