"""Share (%) of the traced window in which no operation ran on the
device, in the batch-1 cells."""
from benchlib.stats import device_idle as read  # noqa: F401
