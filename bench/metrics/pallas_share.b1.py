"""Share (%) of the device's busy time spent inside Pallas kernels
(tpu_custom_call operations) in the traced window."""


def read(ctx):
    tr = ctx.trace
    if tr is None or not tr.busy_ns():
        return None
    return 100.0 * tr.pallas_ns() / tr.busy_ns()
