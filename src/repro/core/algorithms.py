"""Public convolution entry point: run one conv site's Choice.

``conv2d(x, w, choice=...)`` runs a ``repro.core.autotune.Choice`` as
given: its algorithm with its kernel parameters (``block_k``/``block_h``/
``block_c``). This is how a TuningPlan's per-layer decisions reach the
kernels: 'ilpm' | 'direct' | 'im2col' | 'libdnn' | 'winograd' run the
corresponding dense kernels, 'depthwise' | 'pointwise' the grouped family
(MobileNet-style nets), and 'xla' the lax.conv_general_dilated escape
hatch. A direct caller may pass ``algorithm=`` ('auto', 'xla' or a
kernel's name) instead; ``autotune.route`` turns it into the Choice, and
decides there which kernel fits the site.

The optional fused epilogue — ``scale``/``bias`` (folded BatchNorm, (K,)
vectors) and ``act`` ('relu' | 'relu6' | 'swish' | 'sigmoid') — is threaded
through dispatch into the kernels, which apply it inside their output
write: conv+BN+act costs one HBM pass instead of three. A squeeze-and-
excitation block (EfficientNet) adds two operands: ``pool`` makes its
depthwise conv return its per-channel spatial sums as well, written by the
depthwise kernel's epilogue, and ``gate`` scales its projection's input
channels inside the pointwise kernel. Every other Choice gets the
identical math as separate jnp ops. ``u`` optionally carries a precomputed
Winograd filter transform (see ``InferenceEngine``: computed once per plan
build).

Grouped convs are detected from the filter shape: HWIO filters carry
``C // groups`` channels on their input axis, so ``groups`` is the ratio of
image channels to filter depth.
"""
from __future__ import annotations

from repro.core import autotune
from repro.core.convspec import ConvSpec
from repro.kernels import ops, ref


def conv2d(x, w, *, stride=1, padding="SAME", algorithm="auto", impl="auto",
           choice=None, scale=None, bias=None, act=None, u=None, pool=False,
           gate=None):
    """x: (B,H,W,C) NHWC; w: (R,S,C/groups,K) HWIO -> (B,H',W',K).

    Runs ``choice``, or ``autotune.route`` of ``algorithm`` at this call's
    spec when no choice is given. The squeeze-and-excitation operands:
    with ``pool`` the call returns ``(y, sums)``, ``sums`` the (B, K) fp32
    spatial sums of ``y``, which the depthwise kernel writes from its
    epilogue; ``gate`` (B, C) scales ``x``'s channels before the conv,
    inside the pointwise kernel. Every other Choice computes the same in
    jnp around its conv.
    """
    ep = dict(scale=scale, bias=bias, act=act)
    if choice is None:
        spec = ConvSpec.from_tensors(x, w, stride, pool=pool,
                                     gated=gate is not None)
        choice = autotune.route(
            spec, algorithm, epilogue=any(v is not None for v in ep.values()))
    algorithm, params = choice.algorithm, dict(choice.params)
    if gate is not None:
        if algorithm == "pointwise":
            params["gate"] = gate
        else:
            x = ref.gate_channels(x, gate)
    if pool and algorithm == "depthwise":
        params["pool"] = True
    if u is not None and algorithm == "winograd":
        params["u"] = u

    if algorithm == "xla":
        y = ref.apply_epilogue(ref.conv2d_reference(
            x, w, stride=stride, padding=padding,
            groups=x.shape[-1] // w.shape[2]), **ep)
    else:
        if algorithm == "pointwise" or padding == "VALID":
            xp = x  # the pointwise kernel takes the raw image
        elif padding == "SAME":
            xp = ref.pad_same(x, *w.shape[:2], stride=stride)
        else:
            raise ValueError(padding)
        y = ops.dispatch(algorithm, xp, w, impl=impl, stride=stride, **ep,
                         **params)
    if pool and algorithm != "depthwise":
        y = (y, ref.channel_sums(y))
    return y


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
