"""EfficientNet configs — the mobile CNN with squeeze-and-excitation.

EfficientNet-B0 (Tan & Le 2019, arXiv:1905.11946, Table 1) at its published
224x224: a 3x3/2 stem of width 32, then 16 MBConv blocks in seven rows, each
(expand, kernel, stride, out, repeats) — a 1x1 expansion to ``expand`` times
the block's input width (left out where it is 1), a kernel x kernel
depthwise conv carrying the stride (the first block of a row only),
squeeze-and-excitation on its output with a width of a quarter of the
block's input width, and a linear 1x1 projection, with an identity add
where the stride is 1 and the width is kept — then a 1x1 conv to 1280,
global pooling and a 1000-way classifier. Swish follows every conv but the
projections. 5,288,548 parameters with batch norm folded, 0.386 GMAC an
image. It runs on the inverted-residual family module
(``repro.models.mobilenet``).
"""
from repro.configs.base import ArchConfig, register

# Table 1, stages 2-8: (expand, kernel, stride, out channels, repeats)
EFFICIENTNET_B0_ROWS = (
    (1, 3, 1, 16, 1),
    (6, 3, 2, 24, 2),
    (6, 5, 2, 40, 2),
    (6, 3, 2, 80, 3),
    (6, 5, 1, 112, 3),
    (6, 5, 2, 192, 4),
    (6, 3, 1, 320, 1),
)

EFFICIENTNET_B0 = register(ArchConfig(
    name="efficientnet_b0",
    family="cnn",
    num_layers=82,  # weight layers: 49 convs, 32 SE FCs, the classifier
    vocab_size=1000,  # ImageNet classes
    use_ilpm_conv=True,
    dtype="float32",
    param_sharding="replicated",
    extra={"arch": "efficientnet", "img": 224, "stem": 32, "head": 1280,
           "rows": EFFICIENTNET_B0_ROWS, "se_ratio": 0.25, "act": "swish"},
))
