"""The whole step's share (%) of the chip's peak FLOP/s: the layer table's
FLOPs per image times images answered in the traced window, over its
seconds, over the peak for the device kind."""
from benchlib.stats import model_flops_utilization as read  # noqa: F401
