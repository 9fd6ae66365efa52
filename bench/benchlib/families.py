"""Kernel families of a device trace, and the least time their layer-table
rows need: the squeeze-and-excitation (SE) kernels of EfficientNet.

A family is the name a device trace gives a kernel's operations
(``trace.op_family``). The served EfficientNet runs each depthwise conv
whose output feeds an SE as ``depthwise_pool`` (it also writes the
output's per-channel spatial sums) and each projection that an SE gate
scales as ``pointwise_gated``. The layer table marks those rows ``pool``
and ``gated``; no other configuration has them, so in its traces these
families are absent and the readers return None.

A family's least time is its rows' FLOPs over the peak, for each image of
a run of the forward. No byte count bounds it: XLA keeps the operands of a
kernel inside a program in VMEM where they fit (memory-space assignment),
so a kernel need not move any of its bytes through HBM within its own
time. On a TPU v5e a gated projection at 112x112x32 moves its 2.4 MB in
2.4 us, faster than HBM's 819 GB/s could carry them.
"""
from __future__ import annotations

from benchlib.model import conv_flops
from benchlib.trace import union

# family -> the layer-table key that marks its rows
FAMILIES = {"depthwise_pool": "pool", "pointwise_gated": "gated"}


def rows_of(model, family: str) -> list:
    return [r for r in model.rows if r.get(FAMILIES[family])]


def least_seconds(model, family: str, batch: float, peaks: dict) -> float:
    """The least time one run of the forward over ``batch`` images needs
    in ``family``'s kernels: their FLOPs over the peak."""
    return batch * sum(conv_flops(r) for r in rows_of(model, family)) \
        / peaks["peak_flops"]


def family_ns(trace, names) -> int:
    """Device time (ns) in the window covered by operations of ``names``."""
    return sum(e - s for s, e in union(
        [o for o in trace.ops if o.name in names], *trace.window))


def _runs_and_batch(ctx):
    """(forward runs that started in the window, the mean real batch of the
    window's dispatches), or None where the run has nothing to read."""
    tr = ctx.trace
    if tr is None or not ctx.peaks or not ctx.dispatches:
        return None
    runs = [r for r in tr.forward_runs()
            if tr.window[0] <= r.start < tr.window[1]]
    if not runs:
        return None
    b = sum(k * n for k, n in ctx.dispatches.items()) \
        / sum(ctx.dispatches.values())
    return len(runs), b


def roofline(ctx, family: str):
    """Share (%) of ``family``'s device time in the window that its rows
    need at the least, each run of the forward charged at the window's
    mean real batch."""
    got = _runs_and_batch(ctx)
    if got is None or not rows_of(ctx.cell.model, family):
        return None
    ns = family_ns(ctx.trace, {family})
    if not ns:
        return None
    runs, b = got
    least = least_seconds(ctx.cell.model, family, b, ctx.peaks)
    return 100.0 * runs * least / (ns / 1e9)


def se_share(ctx):
    """Share (%) of the device's busy time in the window spent in the
    SE-carrying kernel families."""
    tr = ctx.trace
    if tr is None or not tr.busy_ns():
        return None
    ns = family_ns(tr, set(FAMILIES))
    if not ns:
        return None
    return 100.0 * ns / tr.busy_ns()
