"""Spans and instants of the served path, on the host clock.

``span(name, rid=None, **attrs)`` times its body on `time.perf_counter` and
keeps one :class:`Record` when the body ends, raised or not; ``parent`` is
the id of the span it is nested in (a `contextvars` variable tracks it, so
each thread nests its own).  The same call opens a
`jax.profiler.TraceAnnotation` of the name, with ``rid`` and ``attrs`` as
its stats, which puts the span on the profiler's clock beside the device's
operations whenever a profile is being taken.  ``event`` keeps an instant.

Records go into one bounded deque: a long-running server holds at most
:data:`MAX_RECORDS` of them, the newest.  Recording is always on.

Names are ``<layer>.<what>`` (``engine.step``, ``executor.decode.fetch``);
the benchmark's own spans are ``bench.*``, and no span here takes that
prefix.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import time
from typing import Dict, List, NamedTuple, Optional

from jax.profiler import TraceAnnotation

# ~3,000 records per wave of the benchmark's decode cells (10 per decode
# step): the newest ten waves or so
MAX_RECORDS = 1 << 15


class Record(NamedTuple):
    name: str
    start: float                 # perf_counter seconds
    end: float                   # == start for an event
    parent: Optional[int]        # id of the enclosing span
    rid: Optional[int]           # the request it belongs to, if one
    attrs: Dict
    id: int


_records: collections.deque = collections.deque(maxlen=MAX_RECORDS)
_ids = itertools.count(1)
_current: contextvars.ContextVar = contextvars.ContextVar(
    "telemetry_span", default=None)


def _stats(rid, attrs: Dict) -> Dict:
    return attrs if rid is None else {**attrs, "rid": rid}


class span:
    """Context manager: one timed, nested span (see the module's doc)."""

    __slots__ = ("name", "rid", "attrs", "id", "parent", "start", "_token",
                 "_annotation")

    def __init__(self, name: str, rid: Optional[int] = None, **attrs):
        self.name, self.rid, self.attrs = name, rid, attrs

    def __enter__(self) -> "span":
        self.id = next(_ids)
        self.parent = _current.get()
        self._token = _current.set(self.id)
        self._annotation = TraceAnnotation(self.name,
                                           **_stats(self.rid, self.attrs))
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        end = time.perf_counter()
        self._annotation.__exit__(*exc)
        _current.reset(self._token)
        _records.append(Record(self.name, self.start, end, self.parent,
                               self.rid, self.attrs, self.id))
        return False


def event(name: str, rid: Optional[int] = None, **attrs) -> None:
    """Keep an instant (``start == end``), inside the current span."""
    with TraceAnnotation(name, **_stats(rid, attrs)):
        t = time.perf_counter()
    _records.append(Record(name, t, t, _current.get(), rid, attrs,
                           next(_ids)))


def records() -> List[Record]:
    """The kept records, in the order their spans ended."""
    return list(_records)


def clear() -> None:
    _records.clear()
