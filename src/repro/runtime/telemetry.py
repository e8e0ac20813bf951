"""Host spans of the serving path: one process-wide recorder.

``span(name, **attrs)`` is a context manager around one piece of host work.
It does two things:

- it enters ``jax.profiler.TraceAnnotation(name, **attrs)``, so that in a
  profiled run the span sits on the device trace's own clock, beside the
  device operations it waited for or launched;
- it appends a record to a bounded in-memory deque, readable at any time
  with :func:`spans`, on the host clock (``time.perf_counter``).

Recording is always on and bounded (:data:`MAX_SPANS` records; the oldest
go first).  The annotation costs anything only while a profiler session
runs.  Spans are opened from one thread at a time (the server's loop), so
a span's parent is the span open around it.

Attribute values are ints or short strings without commas (the profiler
reads a comma as the end of an attribute); request ids go as ``"3 5 7"``.
Attributes set on the yielded record after entry (``sp.attrs[k] = v``) go
to the record only, not to the annotation.

Counters stay where they are counted (``SolServer.stats``,
``packed.TRANSFER_STATS``); a counter's increment also goes as an
attribute on the span where it is counted.
"""
from __future__ import annotations

import collections
import itertools
import time
from typing import Deque, Dict, List, Optional, Tuple, Union

import jax

MAX_SPANS = 65_536

Attr = Union[int, str]
# (name, t0, t1, parent, attrs): perf_counter seconds, t1 None while open;
# parent is the index of the enclosing span in the same list, None at the
# top level or when the parent has left the deque
Record = Tuple[str, float, Optional[float], Optional[int], Dict[str, Attr]]

_SPANS: "Deque[span]" = collections.deque(maxlen=MAX_SPANS)
_open: List[int] = []           # sequence numbers of the open spans
_seq = itertools.count()


class span:
    """``with span("sol.stage", bytes=n) as sp:`` records one host span."""

    __slots__ = ("name", "attrs", "t0", "t1", "parent", "seq", "_note")

    def __init__(self, name: str, **attrs: Attr):
        self.name = name
        self.attrs = attrs
        self.t0: float = 0.0
        self.t1: Optional[float] = None

    def __enter__(self) -> "span":
        self._note = jax.profiler.TraceAnnotation(self.name, **self.attrs)
        self._note.__enter__()
        self.parent = _open[-1] if _open else None
        self.seq = next(_seq)
        _open.append(self.seq)
        _SPANS.append(self)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.perf_counter()
        _open.pop()
        self._note.__exit__(*exc)
        self._note = None           # the deque keeps the record, not this


def spans() -> List[Record]:
    """A copy of the recorded spans, oldest first."""
    recs = list(_SPANS)
    first = recs[0].seq if recs else 0
    return [(s.name, s.t0, s.t1,
             s.parent - first if s.parent is not None and s.parent >= first
             else None, dict(s.attrs)) for s in recs]

