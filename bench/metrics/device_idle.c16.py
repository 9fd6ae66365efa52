"""``device_idle.b1``'s reading, in the cells under 16 clients."""
from benchlib.stats import device_idle as read  # noqa: F401
