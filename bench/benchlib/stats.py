"""Arithmetic that several metric readers share."""
from __future__ import annotations

import numpy as np


def latency_percentile(window, q):
    """The q-th percentile (ms) of the latencies of the requests due in the
    window that were answered."""
    lats = [r.latency for r in window.sent()
            if r.error is None and r.latency is not None]
    if not lats:
        return None
    return float(np.percentile(np.asarray(lats) * 1e3, q))


def model_flops_utilization(ctx):
    if not ctx.peaks:
        return None
    done = ctx.window.completed_in_window()
    flops = ctx.cell.model.flops_per_image * done / ctx.window.seconds
    return 100.0 * flops / ctx.peaks["peak_flops"]


def forward_roofline(ctx):
    """The least time the forwards that started in the traced window need,
    over the device's busy time there. Each run of the forward is charged
    for the mean real batch of the window's dispatches; the bound is convex
    in the batch, so that charge is never above the true least time."""
    tr = ctx.trace
    if tr is None or not ctx.peaks or not ctx.dispatches or not tr.busy_ns():
        return None
    runs = [r for r in tr.forward_runs() if r.start >= tr.window[0]
            and r.start < tr.window[1]]
    if not runs:
        return None
    m, pk = ctx.cell.model, ctx.peaks
    b = sum(k * n for k, n in ctx.dispatches.items()) \
        / sum(ctx.dispatches.values())
    least_s = max(b * m.flops_per_image / pk["peak_flops"],
                  (m.weight_bytes + b * (m.image_bytes + m.logit_bytes))
                  / pk["hbm_bytes_per_s"])
    return 100.0 * len(runs) * least_s / (tr.busy_ns() / 1e9)


def device_idle(ctx):
    """Share (%) of the traced window in which no operation ran on the
    device: 1 minus the union of device-op intervals over the window."""
    if ctx.trace is None or not ctx.trace.ops:
        return None
    return 100.0 * ctx.trace.idle_share()
