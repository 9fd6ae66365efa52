"""ResNet-18 at 224x224, float32: layer table and plain reference forward.

Written from He et al. 2016, Table 1 (18-layer): a 7x7/2 conv of width 64
and a 3x3/2 max-pool, then four stages of two basic blocks (two 3x3 convs
each) of widths 64, 128, 256 and 512, the first block of stages 2 to 4
entering with stride 2 and a 1x1 projection shortcut (option B), then
global average pooling and a 1000-way dense classifier. Batch norm is in
its inference form, a per-channel scale and bias after every conv.
Padding is TF-style SAME throughout (the paper does not state it).

``cfg`` is the dict of ``resnet18-224-fp32.json``. Parameter names follow
the served network's parameter tree, so the benchmark can hand the weights
it draws to the system under test: ``stem``, ``s<stage>b<block>.c1|c2|proj``
(each ``w`` HWIO, ``scale``, ``bias``) and ``fc`` (``w``, ``b``).
"""
from benchlib.refops import act, add, classify, conv_bn, max_pool


def blocks(cfg):
    """(name, cin, cout, stride) per basic block."""
    cin = cfg["stem"]["width"]
    for si, stage in enumerate(cfg["stages"]):
        for bi in range(stage["blocks"]):
            stride = stage["stride"] if bi == 0 else 1
            yield f"s{si}b{bi}", cin, stage["width"], stride
            cin = stage["width"]


def _conv(name, k, stride, cin, cout, hw, activation):
    out = -(-hw // stride)
    return {"name": name, "op": "conv", "kernel": k, "stride": stride,
            "cin": cin, "cout": cout, "groups": 1, "in_hw": hw,
            "out_hw": out, "act": activation}


def layers(cfg):
    """The layer table: one row per conv, then the classifier."""
    stem = cfg["stem"]
    hw = cfg["image"][0]
    rows = [_conv("stem", stem["kernel"], stem["stride"], cfg["image"][2],
                  stem["width"], hw, "relu")]
    hw = -(-rows[0]["out_hw"] // stem["pool"]["stride"])
    for name, cin, cout, stride in blocks(cfg):
        if stride != 1 or cin != cout:
            rows.append(_conv(f"{name}.proj", 1, stride, cin, cout, hw, None))
        rows.append(_conv(f"{name}.c1", 3, stride, cin, cout, hw, "relu"))
        hw = -(-hw // stride)
        rows.append(_conv(f"{name}.c2", 3, 1, cout, cout, hw, None))
        last = cout
    rows.append({"name": "fc", "op": "fc", "cin": last,
                 "cout": cfg["classes"]})
    return rows


def forward(params, x, cfg, p):
    """images (B, H, W, 3) -> logits (B, classes), computed as ``p`` says."""
    stem = cfg["stem"]
    x = conv_bn(x, params["stem"], stem["stride"], p, "relu")
    x = max_pool(x, stem["pool"]["kernel"], stem["pool"]["stride"])
    for name, cin, cout, stride in blocks(cfg):
        b = params[name]
        shortcut = conv_bn(x, b["proj"], stride, p) if "proj" in b else x
        h = conv_bn(x, b["c1"], stride, p, "relu")
        h = conv_bn(h, b["c2"], 1, p)
        x = act(add(h, shortcut, p), "relu")
    return classify(x, params["fc"], p)
