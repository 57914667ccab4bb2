"""Host spans of the serving runtime, kept in a bounded in-memory ring.

``span(name, rid=-1)`` is a context manager that does two things:

* it enters ``jax.profiler.TraceAnnotation(name)``, so in any profile the
  span sits on the host plane, on the device trace's clock, nested under
  the annotations around it;
* it records ``(name, parent, rid, t0, t1)`` on ``time.perf_counter`` in a
  ring of preallocated numpy arrays.  ``parent`` is the sequence number of
  the span open around it (-1 at top level).

``record(name, t0, t1, rid)`` adds a span with explicit times (one that
crosses calls, such as a request's time in the queue); it has no
annotation and no parent.  When the ring is full the oldest spans are
overwritten and counted in ``dropped()``.  ``spans(t0, t1)`` returns the
closed spans whose start falls in ``[t0, t1]``, as arrays, with
``complete`` false where the ring may have overwritten one of them.

The ring is always on: a span costs one annotation (inactive unless a
profile is being taken) and a few array writes.  One ring serves the
process, as the profiler does; ``Ring`` makes a private one.  A ring is
not locked: one thread opens and closes its spans.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict

import jax
import numpy as np

CAPACITY = 1 << 17
DEPTH = 64              # spans open at once


class Ring:
    """A bounded record of spans; see the module docstring."""

    def __init__(self, capacity: int = CAPACITY):
        self.capacity = capacity
        self.name = np.zeros(capacity, np.int32)
        self.parent = np.full(capacity, -1, np.int64)
        self.rid = np.full(capacity, -1, np.int64)
        self.t0 = np.zeros(capacity, np.float64)
        self.t1 = np.full(capacity, np.nan, np.float64)
        self.n = 0                      # spans ever opened or recorded
        self.lost_t0 = -np.inf          # latest start of an overwritten span
        self._names: list = []
        self._ids: Dict[str, int] = {}
        self._spans: Dict[str, "_Span"] = {}
        self._open = np.zeros(DEPTH, np.int64)     # sequence numbers
        self._ann: list = [None] * DEPTH
        self._depth = 0

    def _id(self, name: str) -> int:
        i = self._ids.get(name)
        if i is None:
            i = self._ids[name] = len(self._names)
            self._names.append(name)
        return i

    def _put(self, nid: int, parent: int, rid: int, t0: float,
             t1: float) -> int:
        seq = self.n
        self.n += 1
        i = seq % self.capacity
        if seq >= self.capacity and self.t0[i] > self.lost_t0:
            self.lost_t0 = self.t0[i]
        self.name[i] = nid
        self.parent[i] = parent
        self.rid[i] = rid
        self.t0[i] = t0
        self.t1[i] = t1
        return seq

    def span(self, name: str, rid: int = -1) -> "_Span":
        s = self._spans.get(name)
        if s is None:
            s = self._spans[name] = _Span(self, name)
        s.rid = rid
        return s

    def record(self, name: str, t0: float, t1: float, rid: int = -1) -> None:
        self._put(self._id(name), -1, rid, t0, t1)

    def dropped(self) -> int:
        return max(0, self.n - self.capacity)

    def spans(self, t0: float = -np.inf, t1: float = np.inf) -> dict:
        """Closed spans that start in ``[t0, t1]``, in the order they were
        opened: ``seq``, ``name`` (strings), ``parent`` (a ``seq`` or -1),
        ``rid``, ``t0``, ``t1``; ``complete`` is false where a span that
        started at or after ``t0`` may have been overwritten."""
        held = min(self.n, self.capacity)
        first = self.n - held
        seq = np.arange(first, self.n, dtype=np.int64)
        i = seq % self.capacity
        keep = ((self.t0[i] >= t0) & (self.t0[i] <= t1)
                & ~np.isnan(self.t1[i]))
        seq, i = seq[keep], i[keep]
        names = np.asarray(self._names + [""], dtype=object)
        return {"seq": seq, "name": names[self.name[i]],
                "parent": self.parent[i].copy(), "rid": self.rid[i].copy(),
                "t0": self.t0[i].copy(), "t1": self.t1[i].copy(),
                "complete": bool(self.n <= self.capacity
                                 or self.lost_t0 < t0)}


class _Span:
    """The context manager ``Ring.span`` hands out, one per name and
    reused: a span opened inside another of the same name keeps its own
    state on the ring's stack."""

    __slots__ = ("ring", "label", "nid", "rid")

    def __init__(self, ring: Ring, label: str):
        self.ring, self.label, self.rid = ring, label, -1
        self.nid = ring._id(label)

    def __enter__(self) -> "_Span":
        r = self.ring
        ann = jax.profiler.TraceAnnotation(self.label)
        ann.__enter__()
        d = r._depth
        parent = int(r._open[d - 1]) if d else -1
        r._open[d] = r._put(self.nid, parent, self.rid,
                            perf_counter(), np.nan)
        r._ann[d] = ann
        r._depth = d + 1
        return self

    def __exit__(self, *exc) -> None:
        t = perf_counter()
        r = self.ring
        d = r._depth = r._depth - 1
        seq = int(r._open[d])
        if r.n - seq <= r.capacity:           # not overwritten while open
            r.t1[seq % r.capacity] = t
        ann, r._ann[d] = r._ann[d], None
        ann.__exit__(None, None, None)


RING = Ring()


def span(name: str, rid: int = -1) -> _Span:
    return RING.span(name, rid)


def record(name: str, t0: float, t1: float, rid: int = -1) -> None:
    RING.record(name, t0, t1, rid)


def spans(t0: float = -np.inf, t1: float = np.inf) -> dict:
    return RING.spans(t0, t1)


def dropped() -> int:
    return RING.dropped()
