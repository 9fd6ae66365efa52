"""The inverted-residual family: MobileNetV2 and EfficientNet-B0.

Inverted-residual blocks (expand 1x1 -> depthwise kxk -> project 1x1) built
entirely from ``repro.core.algorithms.conv2d`` sites, so the whole backbone
runs under the TuningPlan flow exactly like ``resnet.forward``: the strided
dense stem dispatches a strided ilpm/direct kernel, every pointwise site
the pointwise kernel, every depthwise site (stride 1 *and* 2 — the
depthwise kernel downsamples in-kernel) the depthwise kernel, each with its
per-layer tuned block parameters and its BN/activation epilogue fused into
the kernel's output write. Zhang et al. (2020) show the depthwise/pointwise
layer types dominate mobile inference time, which is why they get their own
kernels rather than riding the dense five.

The two networks differ in three things, all read from the config:

  * the depthwise kernel size per row (MobileNetV2: 3x3 everywhere;
    EfficientNet-B0: 3x3 or 5x5);
  * the activation, ``extra["act"]``: ReLU6 (default) or swish;
  * squeeze-and-excitation (Hu et al. 2018) on the depthwise output where
    ``extra["se_ratio"]`` is set (EfficientNet: 0.25 of the block's input
    width): global mean -> FC + bias + act -> FC + bias + sigmoid -> each
    channel scaled by its gate. The depthwise kernel writes the spatial
    sums from its epilogue (``pool``), the two small FCs run as XLA dots
    under ``jax.named_scope("<block>.se")``, and the projection's
    pointwise kernel scales its input tile by the gate (``gate``). The
    fused inverted-residual kernel has no SE, so the tuner never fuses
    such a block.

Config ``extra`` keys: ``settings`` — MobileNetV2's (t, c, n, s) rows
(expansion, out channels, repeats, first-block stride) — or ``rows`` —
EfficientNet's (expand, kernel, stride, out, repeats); ``stem`` / ``head``
widths; ``img`` input size; ``act``; ``se_ratio``; ``arch`` ("mobilenet" or
"efficientnet") routes the engine here.
"""
from __future__ import annotations

import jax

from repro.kernels.ref import apply_act
from repro.models.resnet import _conv, _conv_spec
from repro.models.spec import ParamSpec


def _dw_spec(c, k):
    """Depthwise kxk: HWIO filters (k, k, 1, C) + folded BN."""
    return {"w": ParamSpec((k, k, 1, c), (None, None, None, None)),
            "scale": ParamSpec((c,), (None,), "ones"),
            "bias": ParamSpec((c,), (None,), "zeros")}


def _fc_spec(cin, cout):
    return {"w": ParamSpec((cin, cout), (None, None)),
            "b": ParamSpec((cout,), (None,), "zeros")}


def _rows(cfg):
    """(expand, kernel, stride, out, repeats) per row: EfficientNet's
    ``rows`` as they are, MobileNetV2's ``settings`` as their 3x3 case."""
    if "rows" in cfg.extra:
        return cfg.extra["rows"]
    return [(t, 3, s, c, n) for t, c, n, s in cfg.extra["settings"]]


def _act(cfg):
    return cfg.extra.get("act", "relu6")


def _blocks(cfg):
    """Yield (name, cin, mid, cout, stride, kernel, se) per inverted-
    residual block; ``se`` is the SE width (from the block's input width,
    as the TF reference sizes it), 0 where the network has none."""
    ratio = cfg.extra.get("se_ratio", 0)
    cin = cfg.extra["stem"]
    for si, (t, k, s, c, n) in enumerate(_rows(cfg)):
        for bi in range(n):
            se = max(1, int(ratio * cin)) if ratio else 0
            yield (f"s{si}b{bi}", cin, cin * t, c, s if bi == 0 else 1, k,
                   se)
            cin = c


def model_specs(cfg):
    sp = {"stem": _conv_spec(3, 3, 3, cfg.extra["stem"])}
    for name, cin, mid, cout, _, k, se in _blocks(cfg):
        block = {}
        if mid != cin:  # t == 1 blocks skip the expansion conv
            block["pw1"] = _conv_spec(1, 1, cin, mid)
        block["dw"] = _dw_spec(mid, k)
        if se:
            block["se_r"] = _fc_spec(mid, se)
            block["se_e"] = _fc_spec(se, mid)
        block["pw2"] = _conv_spec(1, 1, mid, cout)
        sp[name] = block
        last = cout
    sp["head"] = _conv_spec(1, 1, last, cfg.extra["head"])
    sp["fc"] = _fc_spec(cfg.extra["head"], cfg.vocab_size)
    return sp


def conv_specs(cfg):
    """(name, ConvSpec) per conv site, keyed like the params — the plan
    enumeration the engine tunes. Walks the exact geometry of ``forward``:
    stem 3x3 stride 2, then per block pw1 (1x1) at the incoming size,
    dw (depthwise kxk, carries the block stride; ``pool`` where an SE
    follows), pw2 (1x1) at the downsampled size (``gated`` where an SE
    gates it); finally the 1x1 head. Every spec carries ``cfg.dtype`` —
    same precision-as-tuning-key contract as ``resnet.conv_specs``."""
    import dataclasses

    from repro.core.convspec import ConvSpec

    img = cfg.extra["img"]
    specs = [("stem", ConvSpec(h=img, w=img, c=3, k=cfg.extra["stem"],
                               stride=2))]
    size = -(-img // 2)
    for name, cin, mid, cout, stride, k, se in _blocks(cfg):
        if mid != cin:
            specs.append((f"{name}.pw1", ConvSpec(h=size, w=size, c=cin,
                                                  k=mid, r=1, s=1)))
        specs.append((f"{name}.dw", ConvSpec(h=size, w=size, c=mid, k=mid,
                                             r=k, s=k, stride=stride,
                                             groups=mid, pool=bool(se))))
        size = -(-size // stride)
        specs.append((f"{name}.pw2", ConvSpec(h=size, w=size, c=mid, k=cout,
                                              r=1, s=1, gated=bool(se))))
        last = cout
    specs.append(("head", ConvSpec(h=size, w=size, c=last,
                                   k=cfg.extra["head"], r=1, s=1)))
    return [(name, dataclasses.replace(sp, dtype=cfg.dtype))
            for name, sp in specs]


def block_specs(cfg):
    """(name, FusedBlockSpec) per inverted-residual block — the block-site
    enumeration the engine hands to ``build_plan(block_specs=...)``. Sites
    are keyed ``<block>.block`` (e.g. "s0b0.block"), disjoint from the
    per-conv keys, so a plan can carry both and the forward prefers the
    fused choice where one exists. Geometry mirrors ``conv_specs`` (the
    post-stem size walk); ``residual`` is set exactly where the forward
    adds the identity (stride 1, cin == cout); ``se`` carries the SE width,
    which keeps the tuner from fusing the block; dtype stamps the key the
    same way as the conv specs.
    """
    from repro.core.convspec import FusedBlockSpec

    size = -(-cfg.extra["img"] // 2)  # post-stem (stride-2) size
    specs = []
    for name, cin, mid, cout, stride, k, se in _blocks(cfg):
        specs.append((f"{name}.block", FusedBlockSpec(
            "inverted_residual", h=size, w=size, cin=cin, mid=mid,
            cout=cout, r=k, s=k, stride=stride,
            residual=(stride == 1 and cin == cout), dtype=cfg.dtype, se=se)))
        size = -(-size // stride)
    return specs


def _se_gate(p, sums, pixels, act):
    """The SE's excitation from the depthwise output's (B, mid) spatial
    sums: mean -> FC + bias + ``act`` -> FC + bias + sigmoid."""
    pooled = sums / pixels
    r = apply_act(pooled @ p["se_r"]["w"] + p["se_r"]["b"], act)
    return apply_act(r @ p["se_e"]["w"] + p["se_e"]["b"], "sigmoid")


def forward(params, cfg, images, *, plan, winograd_u=None):
    """images: (B,H,W,3) NHWC -> logits (B, classes); a single unbatched
    (H,W,3) image maps to (classes,) — same batch-dim tolerance as
    ``resnet.forward``, so the forward is mappable per element.

    ``plan`` maps every conv site's name ("stem", "s0b0.dw", "s1b0.pw1",
    ...) to the ``Choice`` it runs, same contract as ``resnet.forward``:
    a site without an entry is a ``KeyError``; ``winograd_u`` carries
    cached Winograd filter transforms per layer name. Plan lookup is
    trace-time Python, so a jitted forward bakes in per-layer dispatch.
    Activations (ReLU6 or swish) are fused into each conv's epilogue;
    projection convs are linear.

    A ``<block>.block`` plan entry (from ``build_plan(block_specs=...)``)
    overrides the block's 2-3 per-conv entries: the whole inverted
    residual — identity add included — runs as ONE fused dispatch, its
    expanded intermediate never leaving VMEM. A block with SE runs its
    depthwise conv with ``pool``, the gate, and its projection with
    ``gate``.
    """
    from repro.core import algorithms

    single = images.ndim == 3
    if single:
        images = images[None]
    images = images.astype(cfg.dtype)  # compute precision is cfg.dtype
    wu = winograd_u or {}
    act = _act(cfg)
    x = _conv(params["stem"], images, 2, "stem", plan, wu, act=act)
    for name, cin, mid, cout, stride, _, se in _blocks(cfg):
        p = params[name]
        residual = stride == 1 and cin == cout
        bch = plan.get(f"{name}.block")
        if bch is not None:  # tuner fused this site: one dispatch, not 3
            with jax.named_scope(f"{name}.block"):
                x = algorithms.block_inverted_residual(
                    x, p, bch, stride=stride, residual=residual, act=act)
            continue
        h = x
        if "pw1" in p:
            h = _conv(p["pw1"], h, 1, f"{name}.pw1", plan, act=act)
        if se:
            h, sums = _conv(p["dw"], h, stride, f"{name}.dw", plan,
                            act=act, pool=True)
            with jax.named_scope(f"{name}.se"):
                gate = _se_gate(p, sums, h.shape[1] * h.shape[2], act)
            h = _conv(p["pw2"], h, 1, f"{name}.pw2", plan, gate=gate)
        else:
            h = _conv(p["dw"], h, stride, f"{name}.dw", plan, act=act)
            h = _conv(p["pw2"], h, 1, f"{name}.pw2", plan)
        if residual:
            h = h + x
        x = h
    x = _conv(params["head"], x, 1, "head", plan, act=act)
    x = x.mean(axis=(1, 2))
    logits = x @ params["fc"]["w"] + params["fc"]["b"]
    return logits[0] if single else logits
