"""The program's own spans (``repro.serving.spans``) on the device trace's
clock, and the numbers read from them.

A run that records spans stamps two host marks: ``OPEN`` right after the
window's open mark (a tiny device program, ``trace.OPEN_MARK``) has
returned from ``block_until_ready``, and ``CLOSE`` right before the close
mark is dispatched. The open mark's end on the device (``Trace.window[0]``)
less ``OPEN`` is a first offset from host to device time. It is then
fitted to the window's dispatches: each ``engine.ready`` end (the host
seeing its dispatch finish) is matched with the device program that ended
nearest to it, and the offset moves by the median of those lags, until it
settles. The lags' interquartile spread after the fit is the alignment's
error (``span_clock_error_us``): how far, in the middle half of the
dispatches, the host's view of a program's end lies from the device's.

Spans are read by their fields alone (``name``, ``t0_ns``, ``t1_ns``,
``id``, ``parents``, ``attrs``), so a synthetic list checks this module.
"""
from __future__ import annotations

import bisect
import statistics
from collections import defaultdict

from benchlib.trace import gaps

OPEN, CLOSE = "window.open", "window.close"
NO_SPAN = "no span"
FIT_ROUNDS = 4  # a closer offset can match a ready end to another program

# An idle instant covered by several spans goes to the first of them here:
# a compile, then the dispatch's own steps (one at a time on one thread),
# then the request-level spans that hold them.
PRIORITY = ("engine.first_call", "batcher.window", "scheduler.queue",
            "engine.inputs", "engine.call", "engine.outputs", "engine.ready",
            "scheduler.return", "batcher.resolve", "batcher.wait",
            "serve.request")
HOST_DISPATCH = ("engine.inputs", "engine.call", "engine.outputs")


def _ready_lags(ready_ends, program_ends, offset):
    """Each host ``engine.ready`` end, moved by ``offset``, less the device
    program end nearest to it (ns)."""
    lags = []
    for t in ready_ends:
        t += offset
        i = bisect.bisect_left(program_ends, t)
        near = min(program_ends[max(i - 1, 0):i + 1], key=lambda e: abs(e - t))
        lags.append(t - near)
    return lags


def to_device_clock(spans, marks, trace):
    """(the spans moved onto the device's timeline, the alignment's error
    (us), the fit's shift from the open mark's offset (us)). With fewer
    than two dispatches in the window there is nothing to fit: the open
    mark's offset stands and the error is None."""
    offset = trace.window[0] - marks[OPEN]
    ends = sorted(m.end for m in trace.modules)
    ready = [s.t1_ns for s in _in_window(spans, marks, "engine.ready")]
    shift, error_us = 0, None
    if ends and len(ready) >= 2:
        for _ in range(FIT_ROUNDS):
            step = round(statistics.median(
                _ready_lags(ready, ends, offset + shift)))
            shift -= step
            if step == 0:
                break
        q1, _, q3 = statistics.quantiles(
            _ready_lags(ready, ends, offset + shift), n=4)
        error_us = (q3 - q1) / 1e3
    offset += shift
    return [s._replace(t0_ns=s.t0_ns + offset, t1_ns=s.t1_ns + offset)
            for s in spans], error_us, shift / 1e3


def idle_by_span(trace, spans, n=12) -> list:
    """[[span name, seconds]]: the device's idle time in the traced window
    (``spans`` on the device's clock), each instant given to the span of
    highest ``PRIORITY`` that covers it, or to ``NO_SPAN``; summed by name
    and ranked."""
    rank = {name: i for i, name in enumerate(PRIORITY)}
    edges = sorted(e for s in spans if s.name in rank and s.t1_ns > s.t0_ns
                   for e in ((s.t0_ns, 1, rank[s.name]),
                             (s.t1_ns, -1, rank[s.name])))
    open_count = [0] * len(PRIORITY)
    total = defaultdict(int)
    i = 0
    for lo, hi in gaps(trace.busy(), *trace.window):
        t = lo
        while t < hi:
            while i < len(edges) and edges[i][0] <= t:
                open_count[edges[i][2]] += edges[i][1]
                i += 1
            nxt = min(hi, edges[i][0]) if i < len(edges) else hi
            top = next((PRIORITY[k] for k, c in enumerate(open_count) if c),
                       NO_SPAN)
            total[top] += nxt - t
            t = nxt
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def _in_window(spans, marks, name):
    return [s for s in spans if s.name == name
            and marks[OPEN] <= s.t0_ns < marks[CLOSE]]


def _median_ms(values):
    return statistics.median(values) / 1e6 if values else None


def batcher_wait_ms(spans, marks):
    """Median, over the window's requests, of arrival -> the batcher's loop
    takes the batch (its wake-up and the batching window together)."""
    return _median_ms([s.t1_ns - s.t0_ns
                       for s in _in_window(spans, marks, "batcher.wait")])


def host_dispatch_ms(spans, marks):
    """Median, over the window's dispatches, of the host's time to put one
    on the device: inputs to device arrays, the jitted call, the output
    slices (``HOST_DISPATCH``)."""
    ids = {s.id for s in _in_window(spans, marks, "engine.call")}
    per = defaultdict(int)
    for s in spans:
        if s.name in HOST_DISPATCH and s.id in ids:
            per[s.id] += s.t1_ns - s.t0_ns
    return _median_ms(list(per.values()))


def handoff_ms(spans, marks, held):
    """Median, over the window's requests, of the time lost passing work
    between threads: the dispatch's ``scheduler.queue`` and
    ``scheduler.return``, and the answer from its future to the client
    (``held``: request id -> the client's stamp, ns, as it holds it)."""
    sched = defaultdict(int)
    for s in spans:
        if s.name in ("scheduler.queue", "scheduler.return"):
            sched[s.id] += s.t1_ns - s.t0_ns
    dispatch_of = {r: s.id for s in spans if s.name == "engine.call"
                   for r in s.parents}
    out = []
    for s in _in_window(spans, marks, "serve.request"):
        if s.id in held and s.id in dispatch_of:
            out.append(sched[dispatch_of[s.id]] + held[s.id] - s.t1_ns)
    return _median_ms(out)


def engine_build_s(spans, marks):
    """Seconds of set-up spent building engines and in each entry's and
    bucket's first call (compile or cache load), before the window."""
    ns = sum(s.t1_ns - s.t0_ns for s in spans
             if s.name in ("engine.build", "engine.first_call")
             and s.t1_ns <= marks[OPEN])
    return ns / 1e9 if ns else None


def dispatch_counters(spans, marks):
    """The window's dispatch counters (``engine.call`` attributes), summed,
    with the dispatch count: {"dispatches", "batch", "padded",
    "h2d_bytes"}."""
    calls = _in_window(spans, marks, "engine.call")
    out = defaultdict(int, dispatches=len(calls))
    for s in calls:
        for k, v in (s.attrs or {}).items():
            out[k] += v
    return dict(out)


def span_medians_ms(spans, marks):
    """{span name: median duration (ms)} over the spans that start in the
    window: one a request or one a dispatch, as the span's unit is."""
    per = defaultdict(list)
    for s in spans:
        if marks[OPEN] <= s.t0_ns < marks[CLOSE]:
            per[s.name].append(s.t1_ns - s.t0_ns)
    return {name: _median_ms(v) for name, v in sorted(per.items())}
