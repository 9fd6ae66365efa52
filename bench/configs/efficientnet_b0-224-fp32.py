"""EfficientNet-B0 at 224x224, float32: layer table and plain reference
forward.

Written from Tan and Le 2019, Table 1: a 3x3/2 conv of width 32, then the
MBConv rows (expand, kernel, stride, out, repeats) -- ``repeats`` blocks of
output width ``out``, the first with stride ``stride``, each an expansion
1x1 to ``expand`` times its input width (left out where it is 1), a
kernel x kernel depthwise conv carrying the stride, squeeze-and-excitation
on the depthwise output and a linear 1x1 projection, with an identity add
where the stride is 1 and the width is kept -- then a 1x1 conv of width
1280, global average pooling and a 1000-way dense classifier. Swish follows
every conv but the projections. The squeeze-and-excitation takes the
global mean of each channel, a dense layer with bias to
max(1, int(0.25 x the block's input width)) and swish, a dense layer with
bias back to the expanded width and a sigmoid, and scales each channel by
that gate. Batch norm is in its inference form, a per-channel scale and
bias after every conv. Padding is TF-style SAME throughout.

``cfg`` is the dict of ``efficientnet_b0-224-fp32.json``. Parameter names
follow the served network's parameter tree: ``stem``,
``s<row>b<block>.pw1|dw|pw2`` (each ``w`` HWIO, the depthwise one
(k, k, 1, C), ``scale``, ``bias``), ``s<row>b<block>.se_r|se_e`` (``w``
(in, out), ``b``), ``head`` and ``fc`` (``w``, ``b``).
"""
import jax.numpy as jnp

from benchlib.refops import add, bn, classify, conv, store


def blocks(cfg):
    """(name, cin, mid, cout, stride, kernel, se) per MBConv block."""
    cin = cfg["stem"]["width"]
    for si, (t, k, s, c, n) in enumerate(cfg["rows"]):
        for bi in range(n):
            se = max(1, int(cfg["se_ratio"] * cin))
            yield f"s{si}b{bi}", cin, cin * t, c, s if bi == 0 else 1, k, se
            cin = c


def _conv(name, k, stride, cin, cout, hw, activation, groups=1, **more):
    return {"name": name, "op": "conv", "kernel": k, "stride": stride,
            "cin": cin, "cout": cout, "groups": groups, "in_hw": hw,
            "out_hw": -(-hw // stride), "act": activation, **more}


def layers(cfg):
    """The layer table: one row per conv and per dense layer. A depthwise
    row whose output feeds an SE carries ``pool``, a projection row whose
    input an SE gate scales carries ``gated``."""
    stem = cfg["stem"]
    hw = cfg["image"][0]
    rows = [_conv("stem", stem["kernel"], stem["stride"], cfg["image"][2],
                  stem["width"], hw, "swish")]
    hw = rows[0]["out_hw"]
    for name, cin, mid, cout, stride, k, se in blocks(cfg):
        if mid != cin:
            rows.append(_conv(f"{name}.pw1", 1, 1, cin, mid, hw, "swish"))
        rows.append(_conv(f"{name}.dw", k, stride, mid, mid, hw, "swish",
                          groups=mid, pool=True))
        hw = -(-hw // stride)
        rows.append({"name": f"{name}.se_r", "op": "fc", "cin": mid,
                     "cout": se, "act": "swish"})
        rows.append({"name": f"{name}.se_e", "op": "fc", "cin": se,
                     "cout": mid, "act": "sigmoid"})
        rows.append(_conv(f"{name}.pw2", 1, 1, mid, cout, hw, None,
                          gated=True))
        last = cout
    rows.append(_conv("head", 1, 1, last, cfg["head"], hw, "swish"))
    rows.append({"name": "fc", "op": "fc", "cin": cfg["head"],
                 "cout": cfg["classes"]})
    return rows


def sigmoid(y):
    return 1.0 / (1.0 + jnp.exp(-y))


def swish(y):
    return y * sigmoid(y)


def dense(x, leaf, p):
    """(B, C) -> (B, K): a dense layer with bias, as a 1x1 conv."""
    y = conv(x[:, None, None, :], leaf["w"][None, None], 1, p)[:, 0, 0]
    return store(y + store(leaf["b"], p), p)


def squeeze_excite(h, b, p):
    """Each channel of ``h`` scaled by its squeeze-and-excitation gate."""
    pooled = store(jnp.mean(h, axis=(1, 2)), p)
    r = store(swish(dense(pooled, b["se_r"], p)), p)
    gate = store(sigmoid(dense(r, b["se_e"], p)), p)
    return store(h * gate[:, None, None, :], p)


def conv_bn_swish(x, leaf, stride, p, groups=1):
    y = bn(conv(x, leaf["w"], stride, p, groups), leaf, p)
    return store(swish(y), p)


def forward(params, x, cfg, p):
    """images (B, H, W, 3) -> logits (B, classes), computed as ``p`` says."""
    x = conv_bn_swish(x, params["stem"], cfg["stem"]["stride"], p)
    for name, cin, mid, cout, stride, k, se in blocks(cfg):
        b = params[name]
        h = conv_bn_swish(x, b["pw1"], 1, p) if "pw1" in b else x
        h = conv_bn_swish(h, b["dw"], stride, p, groups=mid)
        h = squeeze_excite(h, b, p)
        h = bn(conv(h, b["pw2"]["w"], 1, p), b["pw2"], p)
        x = add(h, x, p) if stride == 1 and cin == cout else h
    x = conv_bn_swish(x, params["head"], 1, p)
    return classify(x, params["fc"], p)
