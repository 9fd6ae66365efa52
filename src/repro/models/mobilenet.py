"""MobileNetV2-style net — the mobile workload the paper targets.

Inverted-residual blocks (expand 1x1 -> depthwise 3x3 -> project 1x1) built
entirely from ``repro.core.algorithms.conv2d`` sites, so the whole backbone
runs under the TuningPlan flow exactly like ``resnet.forward``: the strided
dense stem dispatches a strided ilpm/direct kernel, every pointwise site
the pointwise kernel, every depthwise site (stride 1 *and* 2 — the
depthwise kernel downsamples in-kernel) the depthwise kernel, each with its
per-layer tuned block parameters and its ReLU6/BN epilogue fused into the
kernel's output write. Zhang et al. (2020) show the depthwise/pointwise
layer types dominate mobile inference time, which is why they get their own
kernels rather than riding the dense five.

Config ``extra`` keys: ``settings`` — MobileNetV2's (t, c, n, s) rows
(expansion, out channels, repeats, first-block stride); ``stem`` / ``head``
widths; ``img`` input size; ``arch: "mobilenet"`` routes the engine here.
"""
from __future__ import annotations

import jax

from repro.models.resnet import _conv, _conv_spec
from repro.models.spec import ParamSpec


def _dw_spec(c):
    """Depthwise 3x3: HWIO filters (3, 3, 1, C) + folded BN."""
    return {"w": ParamSpec((3, 3, 1, c), (None, None, None, None)),
            "scale": ParamSpec((c,), (None,), "ones"),
            "bias": ParamSpec((c,), (None,), "zeros")}


def _blocks(cfg):
    """Yield (name, cin, mid, cout, stride) per inverted-residual block."""
    cin = cfg.extra["stem"]
    for si, (t, c, n, s) in enumerate(cfg.extra["settings"]):
        for bi in range(n):
            yield (f"s{si}b{bi}", cin, cin * t, c, s if bi == 0 else 1)
            cin = c


def model_specs(cfg):
    sp = {"stem": _conv_spec(3, 3, 3, cfg.extra["stem"])}
    for name, cin, mid, cout, _ in _blocks(cfg):
        block = {}
        if mid != cin:  # t == 1 blocks skip the expansion conv
            block["pw1"] = _conv_spec(1, 1, cin, mid)
        block["dw"] = _dw_spec(mid)
        block["pw2"] = _conv_spec(1, 1, mid, cout)
        sp[name] = block
        last = cout
    sp["head"] = _conv_spec(1, 1, last, cfg.extra["head"])
    sp["fc"] = {"w": ParamSpec((cfg.extra["head"], cfg.vocab_size),
                               (None, None)),
                "b": ParamSpec((cfg.vocab_size,), (None,), "zeros")}
    return sp


def conv_specs(cfg):
    """(name, ConvSpec) per conv site, keyed like the params — the plan
    enumeration the engine tunes. Walks the exact geometry of ``forward``:
    stem 3x3 stride 2, then per block pw1 (1x1) at the incoming size,
    dw (depthwise, carries the block stride), pw2 (1x1) at the downsampled
    size; finally the 1x1 head. Every spec carries ``cfg.dtype`` — same
    precision-as-tuning-key contract as ``resnet.conv_specs``."""
    import dataclasses

    from repro.core.convspec import ConvSpec

    img = cfg.extra["img"]
    specs = [("stem", ConvSpec(h=img, w=img, c=3, k=cfg.extra["stem"],
                               stride=2))]
    size = -(-img // 2)
    for name, cin, mid, cout, stride in _blocks(cfg):
        if mid != cin:
            specs.append((f"{name}.pw1", ConvSpec(h=size, w=size, c=cin,
                                                  k=mid, r=1, s=1)))
        specs.append((f"{name}.dw", ConvSpec(h=size, w=size, c=mid, k=mid,
                                             stride=stride, groups=mid)))
        size = -(-size // stride)
        specs.append((f"{name}.pw2", ConvSpec(h=size, w=size, c=mid, k=cout,
                                              r=1, s=1)))
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
    adds the identity (stride 1, cin == cout); dtype stamps the key the
    same way as the conv specs.
    """
    from repro.core.convspec import FusedBlockSpec

    size = -(-cfg.extra["img"] // 2)  # post-stem (stride-2) size
    specs = []
    for name, cin, mid, cout, stride in _blocks(cfg):
        specs.append((f"{name}.block", FusedBlockSpec(
            "inverted_residual", h=size, w=size, cin=cin, mid=mid,
            cout=cout, stride=stride,
            residual=(stride == 1 and cin == cout), dtype=cfg.dtype)))
        size = -(-size // stride)
    return specs


def forward(params, cfg, images, *, algorithm="auto", plan=None,
            winograd_u=None):
    """images: (B,H,W,3) NHWC -> logits (B, classes); a single unbatched
    (H,W,3) image maps to (classes,) — same batch-dim tolerance as
    ``resnet.forward``, so the forward is mappable per element.

    `plan` maps layer names ("stem", "s0b0.dw", "s1b0.pw1", ...) to
    autotuner `Choice`s, same contract as ``resnet.forward``: a planned
    layer dispatches to its tuned algorithm with its tuned kernel params,
    overriding `algorithm`; `winograd_u` carries cached Winograd filter
    transforms per layer name. Plan lookup is trace-time Python, so a
    jitted forward bakes in per-layer dispatch. Activations are ReLU6
    (the MobileNetV2 nonlinearity), fused into each conv's epilogue;
    projection convs are linear. The strided dense stem runs the strided
    ilpm/direct kernels under the tuner, not the XLA escape hatch.

    A ``<block>.block`` plan entry (from ``build_plan(block_specs=...)``)
    overrides the block's 2-3 per-conv entries: the whole inverted
    residual — identity add included — runs as ONE fused dispatch, its
    expanded intermediate never leaving VMEM.
    """
    from repro.core import algorithms

    single = images.ndim == 3
    if single:
        images = images[None]
    images = images.astype(cfg.dtype)  # compute precision is cfg.dtype
    plan = plan or {}
    wu = winograd_u or {}
    x = _conv(params["stem"], images, 2, algorithm, "stem", plan, wu,
              act="relu6")
    for name, cin, mid, cout, stride in _blocks(cfg):
        p = params[name]
        residual = stride == 1 and cin == cout
        bch = plan.get(f"{name}.block")
        if bch is not None:  # tuner fused this site: one dispatch, not 3
            with jax.named_scope(f"{name}.block"):
                x = algorithms.block_inverted_residual(
                    x, p, bch, stride=stride, residual=residual)
            continue
        h = x
        if "pw1" in p:
            h = _conv(p["pw1"], h, 1, algorithm, f"{name}.pw1", plan,
                      act="relu6")
        h = _conv(p["dw"], h, stride, algorithm, f"{name}.dw", plan,
                  act="relu6")
        h = _conv(p["pw2"], h, 1, algorithm, f"{name}.pw2", plan)
        if residual:
            h = h + x
        x = h
    x = _conv(params["head"], x, 1, algorithm, "head", plan, act="relu6")
    x = x.mean(axis=(1, 2))
    logits = x @ params["fc"]["w"] + params["fc"]["b"]
    return logits[0] if single else logits
