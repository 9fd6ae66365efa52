"""Median device gap (ms) between the end of one run of the served forward
and the start of the next, from the trace: the time the serving path keeps
the chip waiting per batch-1 request."""


def read(ctx):
    if ctx.trace is None:
        return None
    gaps = ctx.trace.forward_gaps_ns()
    if not gaps:
        return None
    gaps.sort()
    return gaps[len(gaps) // 2] / 1e6
