"""``forward_roofline.b1``'s reading, in the cells under 16 clients."""
from benchlib.stats import forward_roofline as read  # noqa: F401
