"""ResNet (paper's evaluation network) — NHWC, inference-folded BatchNorm.

Every convolution — the 7x7/2 stem, every 3x3 (strided stage entries
included), and every 1x1 (bottleneck reduce/expand, projection shortcuts)
— routes through ``repro.core.algorithms`` so the whole backbone runs
under the TuningPlan flow: no conv site is hardwired to the XLA escape
hatch. Each site passes its folded-BN scale/bias and activation into
``conv2d`` so the tuned kernel applies the epilogue inside its output
write (conv+BN+act = one HBM pass). This is the vehicle for the paper's
Fig. 5 / Tables 3-4 reproduction and the single-image inference engine
examples.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.spec import ParamSpec


def _conv_spec(r, s, cin, cout):
    return {"w": ParamSpec((r, s, cin, cout), (None, None, None, None)),
            # folded BN: y = conv(x) * scale + bias
            "scale": ParamSpec((cout,), (None,), "ones"),
            "bias": ParamSpec((cout,), (None,), "zeros")}


def _block_specs(cin, cout, bottleneck, stride):
    if bottleneck:
        mid = cout // 4
        sp = {"c1": _conv_spec(1, 1, cin, mid),
              "c2": _conv_spec(3, 3, mid, mid),
              "c3": _conv_spec(1, 1, mid, cout)}
    else:
        sp = {"c1": _conv_spec(3, 3, cin, cout),
              "c2": _conv_spec(3, 3, cout, cout)}
    if stride != 1 or cin != cout:
        sp["proj"] = _conv_spec(1, 1, cin, cout)
    return sp


def model_specs(cfg):
    blocks = cfg.extra["blocks"]
    bottleneck = cfg.extra["bottleneck"]
    widths = [64, 128, 256, 512]
    if bottleneck:
        widths = [w * 4 for w in widths]
    sp = {"stem": _conv_spec(7, 7, 3, 64)}
    cin = 64
    for si, (n, w) in enumerate(zip(blocks, widths)):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            sp[f"s{si}b{bi}"] = _block_specs(cin, w, bottleneck, stride)
            cin = w
    sp["fc"] = {"w": ParamSpec((cin, cfg.vocab_size), (None, None)),
                "b": ParamSpec((cfg.vocab_size,), (None,), "zeros")}
    return sp


def conv_specs(cfg):
    """(name, ConvSpec) per conv site, keyed like the params — the plan
    enumeration the engine tunes.

    Walks the exact geometry of ``forward``: stem (7x7 stride 2) then
    max-pool (stride 2), then each stage's blocks — the first block of
    stages 1+ enters with stride 2 (carried by c1 for basic blocks, c2 for
    bottlenecks, and the 1x1 projection shortcut), and bottleneck stages
    tune the 3x3 at the bottleneck width (cout // 4). Every site is
    enumerated — stem, strided entries, and 1x1s included — so a tuned
    plan covers 100% of the backbone's conv sites. Every spec carries
    ``cfg.dtype``: precision is part of the tuning key, so a bf16 variant
    tunes (and caches) its own plan.
    """
    import dataclasses

    from repro.core.convspec import ConvSpec

    img = cfg.extra["img"]
    blocks = cfg.extra["blocks"]
    bottleneck = cfg.extra["bottleneck"]
    widths = [64, 128, 256, 512]
    if bottleneck:
        widths = [w * 4 for w in widths]
    specs = [("stem", ConvSpec(h=img, w=img, c=3, k=64, r=7, s=7,
                               stride=2))]
    size = img // 4  # stem stride 2, then 3x3/2 max-pool
    cin = 64
    for si, n in enumerate(blocks):
        cout = widths[si]
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            name = f"s{si}b{bi}"
            if stride != 1 or cin != cout:
                specs.append((f"{name}.proj", ConvSpec(
                    h=size, w=size, c=cin, k=cout, r=1, s=1, stride=stride)))
            if bottleneck:
                mid = cout // 4
                specs.append((f"{name}.c1", ConvSpec(
                    h=size, w=size, c=cin, k=mid, r=1, s=1)))
                specs.append((f"{name}.c2", ConvSpec(
                    h=size, w=size, c=mid, k=mid, stride=stride)))
                specs.append((f"{name}.c3", ConvSpec(
                    h=-(-size // stride), w=-(-size // stride), c=mid,
                    k=cout, r=1, s=1)))
            else:
                specs.append((f"{name}.c1", ConvSpec(
                    h=size, w=size, c=cin, k=cout, stride=stride)))
                specs.append((f"{name}.c2", ConvSpec(
                    h=-(-size // stride), w=-(-size // stride), c=cout,
                    k=cout)))
            size = -(-size // stride)  # SAME: ceil, matching the forward
            cin = cout
    return [(name, dataclasses.replace(sp, dtype=cfg.dtype))
            for name, sp in specs]


def block_specs(cfg):
    """(name, FusedBlockSpec) per residual block — the block-site
    enumeration for ``build_plan(block_specs=...)``, keyed
    ``<block>.block``. Each site is the block's *final* conv (basic c2:
    3x3, bottleneck c3: 1x1 — always stride 1, since stage-entry
    downsampling happens in the earlier conv) with the shortcut add and
    the outer ReLU fused into its output write. Geometry mirrors
    ``conv_specs``; dtype stamps the key identically."""
    from repro.core.convspec import FusedBlockSpec

    blocks = cfg.extra["blocks"]
    bottleneck = cfg.extra["bottleneck"]
    widths = [64, 128, 256, 512]
    if bottleneck:
        widths = [w * 4 for w in widths]
    size = cfg.extra["img"] // 4  # stem stride 2, then 3x3/2 max-pool
    specs = []
    for si, n in enumerate(blocks):
        cout = widths[si]
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            size = -(-size // stride)  # the final conv runs post-stride
            mid = cout // 4 if bottleneck else cout
            rs = 1 if bottleneck else 3
            specs.append((f"s{si}b{bi}.block", FusedBlockSpec(
                "residual_conv", h=size, w=size, cin=mid, mid=mid,
                cout=cout, r=rs, s=rs, residual=True, dtype=cfg.dtype)))
    return specs


def _conv(p, x, stride, site, plan, wu=None, act=None, **se):
    """One conv site, under a named scope of its plan name ``site``: runs
    ``plan[site]``, its folded-BN scale/bias and activation riding into
    the kernel as a fused epilogue (``algorithms.conv2d`` threads them to
    the kernel's output write). ``se`` carries a squeeze-and-excitation
    block's ``pool`` / ``gate`` operands to ``conv2d``."""
    from repro.core import algorithms

    with jax.named_scope(site):
        return algorithms.conv2d(x, p["w"], stride=stride, padding="SAME",
                                 choice=plan[site], scale=p["scale"],
                                 bias=p["bias"], act=act,
                                 u=(wu or {}).get(site), **se)


def _block(p, x, bottleneck, stride, name, plan, wu=None):
    """A ``<name>.block`` plan entry replaces the block's final conv AND
    the shortcut add + outer ReLU with one fused dispatch (see
    ``algorithms.block_residual_conv``), under a named scope of that
    entry's name; otherwise the tail runs as the per-layer conv followed
    by a separate XLA add/ReLU pass."""
    from repro.core import algorithms

    idn = x
    if "proj" in p:
        idn = _conv(p["proj"], x, stride, f"{name}.proj", plan)
    bch = plan.get(f"{name}.block")
    if bottleneck:
        h = _conv(p["c1"], x, 1, f"{name}.c1", plan, act="relu")
        h = _conv(p["c2"], h, stride, f"{name}.c2", plan, wu, act="relu")
        tail = "c3"
    else:
        h = _conv(p["c1"], x, stride, f"{name}.c1", plan, wu, act="relu")
        tail = "c2"
    if bch is not None:
        with jax.named_scope(f"{name}.block"):
            return algorithms.block_residual_conv(h, p[tail], bch, res=idn)
    h = _conv(p[tail], h, 1, f"{name}.{tail}", plan, wu)
    return jax.nn.relu(h + idn)


def forward(params, cfg, images, *, plan, winograd_u=None):
    """images: (B,H,W,3) NHWC -> logits (B, classes); a single unbatched
    (H,W,3) image maps to (classes,).

    ``plan`` maps every conv site's name ("stem", "s0b1.c2", "s1b0.proj",
    ...) to the ``Choice`` it runs — its algorithm with its kernel
    parameters, as the engine's plan gives it — and may add
    ``<block>.block`` entries for fused block tails; a site without an
    entry is a ``KeyError``. ``winograd_u`` maps layer names to cached
    filter transforms ``U = G g Gᵀ`` (computed once per engine build —
    weights are frozen at inference). Plan lookup is trace-time Python,
    so a jitted forward bakes in per-layer dispatch.

    Batch-dim tolerance makes the forward mappable per element: under
    ``jax.vmap`` / ``lax.map`` over an image stack each element arrives
    unbatched, is promoted to a batch of one (the paper's single-image
    shape), and squeezed back on return.
    """
    single = images.ndim == 3
    if single:
        images = images[None]
    images = images.astype(cfg.dtype)  # compute precision is cfg.dtype
    wu = winograd_u or {}
    blocks = cfg.extra["blocks"]
    bottleneck = cfg.extra["bottleneck"]
    x = _conv(params["stem"], images, 2, "stem", plan, wu, act="relu")
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, n in enumerate(blocks):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            x = _block(params[f"s{si}b{bi}"], x, bottleneck, stride,
                       f"s{si}b{bi}", plan, wu)
    x = x.mean(axis=(1, 2))
    logits = x @ params["fc"]["w"] + params["fc"]["b"]
    return logits[0] if single else logits
