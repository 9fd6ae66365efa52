"""Spans on the served request path, kept in memory while recording is on.

Off by default. Each boundary in the serving code reads ``spans.active``
and does nothing more when it is None: no clock read, no allocation, no
lock. ``start()`` turns recording on and returns the ``Recorder``;
``Recorder.stop()`` turns it off and returns what was recorded::

    from repro.serving import spans

    rec = spans.start()
    ...                      # serve
    recorded = rec.stop()    # list of Span, in the order they closed

A ``Span`` is a name, its start and end on ``time.perf_counter_ns()``,
the id of the unit it belongs to, the ids of the units that caused it,
and a few attributes. The units:

* a **request** (``serve.request``, ``batcher.wait``): its
  ``Request.id``; no parents;
* a **dispatch** (``batcher.window``, ``scheduler.queue``,
  ``engine.inputs``, ``engine.call``, ``engine.outputs``,
  ``engine.ready``, ``scheduler.return``, ``batcher.resolve``,
  ``engine.first_call``): an id drawn when the batch is taken; its parents
  are the ids of its requests. ``batcher.window`` carries why the batch
  was taken: ``cause`` (``full``, ``window``, ``lone`` or ``drain``).
  ``engine.call`` carries the dispatch's counters: ``batch`` (real
  images), ``padded`` (the bucket) and ``h2d_bytes`` (image bytes sent
  from the host);
* an **engine build** (``engine.build``): an id of its own, and the built
  plan's counters (``InferenceEngine.plan_counters``): ``sites``,
  ``xla_sites``, ``fused_sites``, ``gated_sites``.

Nothing is written while recording; ``Recorder.mark(name)`` stamps an
instant, so that a caller can put these spans on another clock (a device
trace's) by marks it makes on both.
"""
from __future__ import annotations

import itertools
import time
from typing import NamedTuple

# the recorder while recording is on, else None: the one thing a boundary
# reads when recording is off
active: Recorder | None = None

_IDS = itertools.count(1 << 40)  # dispatch and build ids, apart from
#                                  Request ids (which count from 0)


class Span(NamedTuple):
    name: str
    t0_ns: int
    t1_ns: int
    id: int
    parents: tuple = ()
    attrs: dict | None = None


class Recorder:
    """The spans and marks of one recording. Spans are appended from the
    serving threads (``list.append`` is atomic under the GIL), as plain
    tuples: the garbage collector stops tracking a tuple of numbers and
    strings, so most of a long recording stays out of its collections."""

    def __init__(self):
        self.spans: list[tuple] = []
        self.marks: dict[str, int] = {}

    def add(self, name: str, t0_ns: int, t1_ns: int, id: int,
            parents: tuple = (), attrs: dict | None = None) -> None:
        self.spans.append((name, t0_ns, t1_ns, id, parents, attrs))

    def mark(self, name: str) -> int:
        """Stamp an instant under ``name``; returns the stamp (ns)."""
        t = time.perf_counter_ns()
        self.marks[name] = t
        return t

    def stop(self) -> list[Span]:
        """Turn recording off (if this recorder is the active one) and
        return the spans recorded so far; a dispatch still in flight adds
        its spans to this recorder only."""
        global active
        if active is self:
            active = None
        return [Span(*s) for s in self.spans]


class Dispatch(NamedTuple):
    """What one dispatch's spans are recorded under: taken with the batch
    and handed along to the threads that serve it."""
    rec: Recorder
    id: int
    parents: tuple  # the ids of the dispatch's requests

    def add(self, name: str, t0_ns: int, t1_ns: int,
            attrs: dict | None = None) -> None:
        self.rec.add(name, t0_ns, t1_ns, self.id, self.parents, attrs)


def start() -> Recorder:
    """Turn recording on; raises if a recording is already on."""
    global active
    if active is not None:
        raise RuntimeError("span recording is already on")
    active = Recorder()
    return active


def new_id() -> int:
    """An id for a dispatch or an engine build."""
    return next(_IDS)


def ns(t: float) -> int:
    """A ``time.perf_counter()`` stamp (s) in ``perf_counter_ns`` units."""
    return int(t * 1e9)
