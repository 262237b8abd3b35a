"""Spans and counters of the served path, in memory and on the profiler's
clock.

``span(name, **ids)`` times a block of work.  It opens a
``jax.profiler.TraceAnnotation(name, **ids)``, so that whenever a profiler
trace is recording the span lands on the host plane beside the device ops,
and it appends one :class:`Record` to a bounded process-wide buffer.
``count`` records one counter sample; ``record`` takes a span's ends
stamped elsewhere (a wait that begins on one thread and ends on another).
Every stamp is ``time.monotonic()``.  ``window(lo, hi)`` gives the records
that ended inside ``[lo, hi]``; ``summary()`` totals the held records by
name for an operator.

The recorder has no switch: spans sit at group, batch and plan
granularity, never per tile, box or frame, so their cost stays in the
noise.  A span that encloses other spans (a plan, a group fetch) passes
``profile=False`` and is kept in memory only: the profiler copy holds leaf
spans alone, so each of its events names one step.
"""
from __future__ import annotations

import collections
import math
import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation

#: records the process keeps; the oldest go first
MAX_RECORDS = 1 << 16


class Record(NamedTuple):
    name: str
    t0: float
    t1: float
    value: float        # a span's seconds, or a counter's sample
    ids: dict


class Span:
    """One timed block; ``seconds`` reads the time so far while it is
    open, and its duration once it has closed."""

    __slots__ = ("_recorder", "name", "ids", "t0", "t1", "_ann")

    def __init__(self, recorder: "Recorder", name: str, profile: bool,
                 ids: dict):
        self._recorder, self.name, self.ids = recorder, name, ids
        self._ann = TraceAnnotation(name, **ids) if profile else None
        self.t0 = self.t1 = None

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> None:
        self.t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._recorder.add(Record(self.name, self.t0, self.t1,
                                  self.t1 - self.t0, self.ids))

    @property
    def seconds(self) -> float:
        end = time.monotonic() if self.t1 is None else self.t1
        return end - self.t0


class Recorder:
    """A bounded buffer of records, safe to append to from any thread."""

    def __init__(self, maxlen: int = MAX_RECORDS):
        self._records: collections.deque = collections.deque(maxlen=maxlen)
        self._lock = threading.Lock()
        self._dropped_until = -math.inf     # latest end of a dropped record

    def add(self, rec: Record) -> None:
        with self._lock:
            if len(self._records) == self._records.maxlen:
                self._dropped_until = max(self._dropped_until,
                                          self._records[0].t1)
            self._records.append(rec)

    def span(self, name: str, *, profile: bool = True, **ids) -> Span:
        return Span(self, name, profile, ids)

    def count(self, name: str, value: float, **ids) -> None:
        t = time.monotonic()
        self.add(Record(name, t, t, value, ids))

    def record(self, name: str, t0: float, t1: float, **ids) -> None:
        """A span whose ends were stamped elsewhere; memory only."""
        self.add(Record(name, t0, t1, t1 - t0, ids))

    def window(self, lo: float, hi: float) -> Optional[list[Record]]:
        """The records that ended in ``[lo, hi]``, or ``None`` when records
        that ended at or after ``lo`` were dropped: a reader then reports
        nothing rather than a biased number."""
        with self._lock:
            if self._dropped_until >= lo:
                return None
            held = list(self._records)
        return [r for r in held if lo <= r.t1 <= hi]

    def summary(self) -> dict:
        """``{name: {"count", "total", "max"}}`` over the held records: a
        span's values are seconds, a counter's its samples."""
        with self._lock:
            held = list(self._records)
        out: dict = {}
        for r in held:
            s = out.setdefault(r.name, {"count": 0, "total": 0.0,
                                        "max": -math.inf})
            s["count"] += 1
            s["total"] += r.value
            s["max"] = max(s["max"], r.value)
        return out


_RECORDER = Recorder()
span = _RECORDER.span
count = _RECORDER.count
record = _RECORDER.record
window = _RECORDER.window
summary = _RECORDER.summary
