"""Share (%) of the device's busy time in the traced window spent in the
kernels that carry EfficientNet's squeeze-and-excitation: the depthwise
convs that also write their spatial sums (``depthwise_pool``) and the
projections their gate scales (``pointwise_gated``)."""
from benchlib.families import se_share as read  # noqa: F401
