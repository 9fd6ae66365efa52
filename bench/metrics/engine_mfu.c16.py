"""``engine_mfu.b1``'s reading, in the cells under 16 clients."""
from benchlib.stats import model_flops_utilization as read  # noqa: F401
