"""From a profiler trace to the numbers the per-layer metrics read.

A traced run records the device alone: host tracing also records the TPU
runtime's host-side relayout of every image (some 3,700 events a request),
which slowed the served rate threefold. The window is marked on the
device's own timeline by two tiny programs that the benchmark runs as it
opens and closes (``OPEN_MARK``, ``CLOSE_MARK``).

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into
plain events in nanoseconds: the operations that ran on the device and the
device programs (XLA modules) they belong to. The rest of this module works
on such event lists alone, so a small synthetic list checks it.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

OPEN_MARK = "bench_window_open"
CLOSE_MARK = "bench_window_close"


@dataclass(frozen=True)
class Event:
    start: int   # ns
    end: int     # ns
    name: str
    pallas: bool = False  # a Pallas kernel (a tpu_custom_call)


@dataclass
class Trace:
    window: tuple          # (start, end) ns, between the two marks
    ops: list = field(default_factory=list)      # device operations
    modules: list = field(default_factory=list)  # device program runs

    @property
    def window_ns(self) -> int:
        return self.window[1] - self.window[0]

    def busy(self) -> list:
        return union(self.ops, *self.window)

    def busy_ns(self) -> int:
        return sum(e - s for s, e in self.busy())

    def idle_share(self) -> float:
        return 1.0 - self.busy_ns() / self.window_ns

    def pallas_ns(self) -> int:
        return sum(e - s for s, e in union(
            [o for o in self.ops if o.pallas], *self.window))

    def forward_runs(self) -> list:
        """Runs of the program that took the most device time in the
        window: the served forward, whatever the program calls it."""
        mods = [m for m in self.modules
                if m.end > self.window[0] and m.start < self.window[1]]
        if not mods:
            return []
        total = defaultdict(int)
        for m in mods:
            total[m.name] += m.end - m.start
        top = max(total, key=total.get)
        return sorted((m for m in mods if m.name == top),
                      key=lambda m: m.start)

    def forward_gaps_ns(self) -> list:
        runs = self.forward_runs()
        return [b.start - a.end for a, b in zip(runs, runs[1:])]


def union(events, lo, hi) -> list:
    """Merged (start, end) intervals covered by ``events`` within [lo, hi]."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events
                   if e.end > lo and e.start < hi)
    merged = []
    for s, e in spans:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def gaps(merged, lo, hi) -> list:
    """The idle (start, end) intervals between merged busy intervals."""
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def _ranked(total: dict, n: int) -> list:
    ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in ranked]


def top_ops(trace: Trace, n=10) -> list:
    """[[op, seconds]]: the kinds of device operation that took most time
    in the window."""
    total = defaultdict(int)
    lo, hi = trace.window
    for o in trace.ops:
        total[o.name] += max(0, min(o.end, hi) - max(o.start, lo))
    return _ranked(total, n)


def idle_by_next(trace: Trace, n=10) -> list:
    """[["before <program>", seconds]]: the device's idle time in the
    window by the program that ended each gap (the one running when the
    gap ends, or else the next to start: what the host was getting ready),
    summed and ranked; "window end" where none did."""
    mods = sorted(trace.modules, key=lambda m: m.start)
    starts = [m.start for m in mods]
    total = defaultdict(int)
    for s, e in gaps(trace.busy(), *trace.window):
        i = bisect.bisect_right(starts, e) - 1
        if i < 0 or mods[i].end <= e:
            i += 1
        label = f"before {mods[i].name}" if i < len(mods) \
            and mods[i].start < trace.window[1] else "window end"
        total[label] += e - s
    return _ranked(total, n)


def window_between_marks(ops, modules, where="the trace") -> Trace:
    """What ran between the end of the last ``OPEN_MARK`` and the start of
    the first ``CLOSE_MARK`` after it."""
    opens = [m for m in modules if m.name == OPEN_MARK]
    if not opens:
        raise ValueError(f"no {OPEN_MARK!r} program in {where}")
    lo = max(m.end for m in opens)
    closes = [m.start for m in modules
              if m.name == CLOSE_MARK and m.start >= lo]
    if not closes:
        raise ValueError(f"no {CLOSE_MARK!r} program after it in {where}")
    return Trace((lo, min(closes)), ops, [
        m for m in modules if m.name not in (OPEN_MARK, CLOSE_MARK)])


# ---------------------------------------------------------------------------
# reading the profiler's file

def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def _is_pallas(name: str) -> bool:
    """A Pallas kernel reaches the TPU as a custom call whose target is
    ``tpu_custom_call``; the trace names each op by its HLO text."""
    return 'custom_call_target="tpu_custom_call"' in name


def op_family(name: str) -> str:
    """'%ilpm_conv.17 = f32[...] custom-call(...)' -> 'ilpm_conv': the HLO
    instruction's name without its number, which groups the runs of one
    kind of op across sites and compiles."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def program_name(name: str) -> str:
    """'jit_squeeze(6590498531410389936)' -> 'squeeze'."""
    return re.sub(r"^jit_", "", name.split("(", 1)[0])


def read_xplane(path: str, device="/device:TPU:0") -> Trace:
    from jax.profiler import ProfileData

    ops, modules = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != device:
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                ops += [Event(int(e.start_ns), int(e.end_ns),
                              op_family(e.name), _is_pallas(e.name))
                        for e in line.events]
            elif line.name == "XLA Modules":
                modules += [Event(int(e.start_ns), int(e.end_ns),
                                  program_name(e.name))
                            for e in line.events]
    return window_between_marks(ops, modules, path)
