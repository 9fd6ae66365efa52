"""MobileNetV2 (width 1.0) at 224x224, float32: layer table and plain
reference forward.

Written from Sandler et al. 2018, Table 2: a 3x3/2 conv of width 32, then
the bottleneck rows (t, c, n, s) -- n inverted-residual blocks of output
width c, the first with stride s, each an expansion 1x1 to t times its input
width (left out where t = 1), a 3x3 depthwise conv carrying the stride and a
linear 1x1 projection, with an identity add where the stride is 1 and the
width is kept -- then a 1x1 conv of width 1280, global average pooling and
a 1000-way dense classifier. ReLU6 follows every conv but the projections.
Batch norm is in its inference form, a per-channel scale and bias after
every conv. Padding is TF-style SAME throughout (the paper does not state
it).

``cfg`` is the dict of ``mobilenet_v2-224-fp32.json``. Parameter names
follow the served network's parameter tree: ``stem``,
``s<row>b<block>.pw1|dw|pw2`` (each ``w`` HWIO, the depthwise one
(3, 3, 1, C), ``scale``, ``bias``), ``head`` and ``fc`` (``w``, ``b``).
"""
from benchlib.refops import add, classify, conv_bn


def blocks(cfg):
    """(name, cin, mid, cout, stride) per inverted-residual block."""
    cin = cfg["stem"]["width"]
    for si, (t, c, n, s) in enumerate(cfg["settings"]):
        for bi in range(n):
            yield f"s{si}b{bi}", cin, cin * t, c, s if bi == 0 else 1
            cin = c


def _conv(name, k, stride, cin, cout, hw, activation, groups=1):
    return {"name": name, "op": "conv", "kernel": k, "stride": stride,
            "cin": cin, "cout": cout, "groups": groups, "in_hw": hw,
            "out_hw": -(-hw // stride), "act": activation}


def layers(cfg):
    """The layer table: one row per conv, then the classifier."""
    stem = cfg["stem"]
    hw = cfg["image"][0]
    rows = [_conv("stem", stem["kernel"], stem["stride"], cfg["image"][2],
                  stem["width"], hw, "relu6")]
    hw = rows[0]["out_hw"]
    for name, cin, mid, cout, stride in blocks(cfg):
        if mid != cin:
            rows.append(_conv(f"{name}.pw1", 1, 1, cin, mid, hw, "relu6"))
        rows.append(_conv(f"{name}.dw", 3, stride, mid, mid, hw, "relu6",
                          groups=mid))
        hw = -(-hw // stride)
        rows.append(_conv(f"{name}.pw2", 1, 1, mid, cout, hw, None))
        last = cout
    rows.append(_conv("head", 1, 1, last, cfg["head"], hw, "relu6"))
    rows.append({"name": "fc", "op": "fc", "cin": cfg["head"],
                 "cout": cfg["classes"]})
    return rows


def forward(params, x, cfg, p):
    """images (B, H, W, 3) -> logits (B, classes), computed as ``p`` says."""
    x = conv_bn(x, params["stem"], cfg["stem"]["stride"], p, "relu6")
    for name, cin, mid, cout, stride in blocks(cfg):
        b = params[name]
        h = conv_bn(x, b["pw1"], 1, p, "relu6") if "pw1" in b else x
        h = conv_bn(h, b["dw"], stride, p, "relu6", groups=mid)
        h = conv_bn(h, b["pw2"], 1, p)
        x = add(h, x, p) if stride == 1 and cin == cout else h
    x = conv_bn(x, params["head"], 1, p, "relu6")
    return classify(x, params["fc"], p)
