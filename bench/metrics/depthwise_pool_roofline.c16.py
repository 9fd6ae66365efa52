"""Share (%) of the ``depthwise_pool`` kernels' device time in the traced
window that their layer-table rows need at the least: their FLOPs over the
peak for each image of each run of the forward, at the window's mean real
batch (``benchlib.families`` says why no byte count bounds it)."""
from benchlib.families import roofline


def read(ctx):
    return roofline(ctx, "depthwise_pool")
