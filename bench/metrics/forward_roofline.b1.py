"""Share (%) of the device's busy time that the served forward's work
needs at the least: for each dispatch of B real images, the larger of
B x FLOPs over peak FLOP/s and (weight bytes + B x (image + logit bytes))
over HBM bandwidth, summed, over the busy time in the traced window. It
counts work by the network, not by kernel, so no fusion or renaming can
push it past 100%."""
from benchlib.stats import forward_roofline as read  # noqa: F401
