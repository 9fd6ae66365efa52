"""Public convolution entry point with algorithm selection.

``conv2d(x, w, algorithm=...)`` is how the framework consumes the paper's
contribution: 'ilpm' | 'direct' | 'im2col' | 'libdnn' | 'winograd' run the
corresponding dense kernels; 'depthwise' | 'pointwise' run the grouped
family (MobileNet-style nets); 'auto' asks the autotuner; 'xla' is the
lax.conv_general_dilated escape hatch (grouped-but-not-depthwise convs,
strides > 2). Passing an explicit autotuner ``choice`` (a
``repro.core.autotune.Choice``) pins both the algorithm *and* its tuned
kernel parameters (``block_k``/``block_h``/``block_c``) — this is how a
TuningPlan's per-layer decisions reach the kernels.

Stride 2 stays in a Pallas kernel for every family: ilpm slides tap
windows over the resident image's stride phases and direct over strided
row bands (the ResNet 7x7/2 stem and stage-entry 3x3/2s), pointwise runs
on the subsampled image (1x1/2 projection shortcuts), and depthwise
downsamples in-kernel. Only
im2col/libdnn/winograd are stride-1-only; forcing one of them on a strided
site falls back to ilpm.

The optional fused epilogue — ``scale``/``bias`` (folded BatchNorm, (K,)
vectors) and ``act`` ('relu' | 'relu6' | 'swish' | 'sigmoid') — is threaded
through dispatch into the kernels, which apply it inside their output
write: conv+BN+act costs one HBM pass instead of three. A squeeze-and-
excitation block (EfficientNet) adds two operands: ``pool`` makes its
depthwise conv return its per-channel spatial sums as well, written by the
depthwise kernel's epilogue, and ``gate`` scales its projection's input
channels inside the pointwise kernel. The XLA escape hatch applies the
identical math as separate ops. ``u`` optionally carries a precomputed Winograd
filter transform (see ``InferenceEngine``: computed once per plan build).

Grouped convs are detected from the filter shape: HWIO filters carry
``C // groups`` channels on their input axis, so ``groups`` is the ratio of
image channels to filter depth. Depthwise (groups == C, K = M·C for any
channel multiplier M) dispatches to the depthwise kernel at stride 1 or 2;
other grouped convs fall back to the XLA reference.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import autotune
from repro.core.convspec import ConvSpec
from repro.kernels import ops, ref

# dense kernels with a stride-2 variant
STRIDED_DENSE = ("ilpm", "direct")


def _auto(x, w, stride, epilogue=False, pool=False, gated=False):
    """Trace-time tuner lookup (memoized per ConvSpec). ``epilogue``
    matches the costing to the call: a site dispatching fused BN/act must
    be selected as its fused variant, the same way the engine's plans are
    built (``build_plan(..., epilogue=True)``); so do the squeeze-and-
    excitation flags."""
    spec = ConvSpec.from_tensors(x, w, stride, pool=pool, gated=gated)
    tuned = autotune.select(spec, epilogue=epilogue)
    return tuned.algorithm, dict(tuned.params)


def _gated_in_kernel(x, w, stride, algorithm, choice, ep_on):
    """Whether a gated conv's route ends at the pointwise kernel, which
    applies the gate to its input tile."""
    if w.shape[:3] != (1, 1, x.shape[-1]):
        return False
    if choice is not None:
        algorithm = choice.algorithm
    if algorithm == "auto":
        algorithm, _ = _auto(x, w, stride, epilogue=ep_on, gated=True)
    return algorithm == "pointwise"


def conv2d(x, w, *, stride=1, padding="SAME", algorithm="auto", impl="auto",
           choice=None, scale=None, bias=None, act=None, u=None, pool=False,
           gate=None):
    """x: (B,H,W,C) NHWC; w: (R,S,C/groups,K) HWIO -> (B,H',W',K).

    The squeeze-and-excitation operands: with ``pool`` the call returns
    ``(y, sums)``, ``sums`` the (B, K) fp32 spatial sums of ``y``, which
    the depthwise kernel writes from its epilogue; ``gate`` (B, C) scales
    ``x``'s channels before the conv, inside the pointwise kernel. Every
    other route computes the same in jnp around its conv.
    """
    ep_on = scale is not None or bias is not None or act is not None
    if gate is not None and not _gated_in_kernel(x, w, stride, algorithm,
                                                 choice, ep_on):
        x, gate = ref.gate_channels(x, gate), None
    y = _conv2d(x, w, stride=stride, padding=padding, algorithm=algorithm,
                impl=impl, choice=choice, scale=scale, bias=bias, act=act,
                u=u, pool=pool, gate=gate)
    if pool and not isinstance(y, tuple):  # not the depthwise kernel
        y = (y, ref.channel_sums(y))
    return y


def _conv2d(x, w, *, stride, padding, algorithm, impl, choice, scale, bias,
            act, u, pool, gate):
    R, S, Cg, K = w.shape
    C = x.shape[-1]
    assert C % Cg == 0, f"image channels {C} vs filter depth {Cg}"
    groups = C // Cg
    ep = dict(scale=scale, bias=bias, act=act)
    ep_on = scale is not None or bias is not None or act is not None
    if choice is not None:
        algorithm, params = choice.algorithm, dict(choice.params)
    else:
        params = {}
    if algorithm == "xla":
        return ref.apply_epilogue(
            ref.conv2d_reference(x, w, stride=stride, padding=padding,
                                 groups=groups), **ep)

    # ---- grouped family: depthwise kernel or XLA fallback ------------
    if groups > 1:
        if algorithm == "auto":
            algorithm, params = _auto(x, w, stride, epilogue=ep_on,
                                      pool=pool)
        depthwise_ok = groups == C and K % C == 0 and stride in (1, 2)
        if algorithm != "depthwise" or not depthwise_ok:
            # tuner punted, or a grouped-but-not-depthwise conv
            return ref.apply_epilogue(
                ref.conv2d_reference(x, w, stride=stride, padding=padding,
                                     groups=groups), **ep)
        xp = ref.pad_same(x, R, S, stride=stride) if padding == "SAME" else x
        if pool:
            params["pool"] = True
        return ops.dispatch("depthwise", xp, w, impl=impl, stride=stride,
                            **ep, **params)

    if stride != 1 and (R, S) == (stride, stride) and padding == "VALID":
        # non-overlapping patch conv (ViT patch embed): degenerate ILP-M
        # — a single "tap block", i.e. reshape + matmul, K on lanes.
        B, H, W, _ = x.shape
        hp, wp = H // stride, W // stride
        xr = x[:, :hp * stride, :wp * stride].reshape(
            B, hp, stride, wp, stride, C).transpose(0, 1, 3, 2, 4, 5)
        xr = xr.reshape(B, hp * wp, stride * stride * C)
        y = jnp.einsum("bpc,ck->bpk", xr, w.reshape(-1, K))
        return ref.apply_epilogue(y.reshape(B, hp, wp, K), **ep)

    if algorithm == "auto":
        algorithm, params = _auto(x, w, stride, epilogue=ep_on,
                                  gated=gate is not None)
        if algorithm == "xla":  # tuner punted (e.g. stride > 2)
            return ref.apply_epilogue(
                ref.conv2d_reference(x, w, stride=stride, padding=padding),
                **ep)

    if algorithm == "pointwise":
        if (R, S) != (1, 1):
            algorithm = "ilpm"  # pointwise kernel is 1x1-only -> best dense
        else:
            if gate is not None:
                params["gate"] = gate
            return ops.dispatch("pointwise", x, w, impl=impl, stride=stride,
                                **ep, **params)

    if stride != 1 and algorithm not in STRIDED_DENSE:
        # im2col/libdnn/winograd have no strided kernels -> best strided
        algorithm = "ilpm"

    if padding == "SAME":
        xp = ref.pad_same(x, R, S, stride=stride)
    elif padding == "VALID":
        xp = x
    else:
        raise ValueError(padding)

    if algorithm == "winograd":
        H, W = xp.shape[1] - R + 1, xp.shape[2] - S + 1
        if (R, S) != (3, 3) or H % 2 or W % 2:
            algorithm = "ilpm"  # winograd F(2,3) inapplicable -> best direct
        elif u is not None:
            params["u"] = u
    return ops.dispatch(algorithm, xp, w, impl=impl, stride=stride,
                        **ep, **params)


# ---- fused blocks: one dispatch where the per-layer path makes 2-3 ----

def block_inverted_residual(x, p, choice, *, stride=1, residual=False,
                            act="relu6", impl="auto"):
    """Run a whole inverted-residual block as one fused dispatch.

    ``p`` is the model's param subtree for the block — optional ``pw1``
    plus ``dw``/``pw2``, each a ``{"w", "scale", "bias"}`` conv site —
    flattened here into the stage-keyed weights dict the block kernel
    takes. ``choice`` is the plan's block-site Choice (algorithm +
    tuned ``block_m``); activations follow the network's pattern (``act``
    after the expansion and depthwise convs, a linear projection), so
    they're call-site constants, not plan state. A block with a squeeze-
    and-excitation gate never gets here: the tuner does not fuse it.
    """
    weights = {"wdw": p["dw"]["w"], "sdw": p["dw"]["scale"],
               "bdw": p["dw"]["bias"],
               "w2": p["pw2"]["w"], "s2": p["pw2"]["scale"],
               "b2": p["pw2"]["bias"]}
    if "pw1" in p:
        weights.update({"w1": p["pw1"]["w"], "s1": p["pw1"]["scale"],
                        "b1": p["pw1"]["bias"]})
    return ops.dispatch_block(choice.algorithm, x, weights, impl=impl,
                              stride=stride, residual=residual, act=act,
                              out_act=None, **dict(choice.params))


def block_residual_conv(x, p, choice, *, res, impl="auto"):
    """Run a ResNet block's final conv with the shortcut add + outer ReLU
    fused into its output write. ``p`` is the conv's ``{"w", "scale",
    "bias"}`` site; ``res`` the identity/projection branch; SAME padding
    applied here (the fused kernel is stride-1 by construction)."""
    w = p["w"]
    xp = ref.pad_same(x, w.shape[0], w.shape[1])
    weights = {"w": w, "scale": p["scale"], "bias": p["bias"]}
    return ops.dispatch_block(choice.algorithm, xp, weights, impl=impl,
                              res=res, act="relu", **dict(choice.params))
