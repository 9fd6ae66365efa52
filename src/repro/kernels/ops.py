"""jit'd public wrappers over the Pallas kernels, with dispatch.

TPU is the TARGET; this container is CPU-only. Policy:
  * ``impl='pallas'`` runs the Pallas kernels (interpret=True off-TPU) —
    used by the kernel tests/benchmarks;
  * ``impl='jnp'`` runs the structural jnp references — used inside model
    forward passes so the 512-device dry-run lowers plain XLA HLO;
  * ``impl='auto'`` picks pallas on TPU, jnp elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels import causal_conv1d as _cc
from repro.kernels import depthwise_conv as _dw
from repro.kernels import fused_block as _fb
from repro.kernels import direct_conv as _dc
from repro.kernels import ilpm_conv as _il
from repro.kernels import im2col_conv as _im
from repro.kernels import libdnn_conv as _lib
from repro.kernels import pointwise_conv as _pw
from repro.kernels import winograd_conv as _wg
from repro.kernels.gemm import gemm  # noqa: F401  (public)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _use_pallas(impl: str) -> bool:
    if impl == "auto":
        return _on_tpu()
    return impl == "pallas"


def _interp() -> bool:
    return not _on_tpu()


# ---- the conv algorithms (pre-padded inputs) -------------------------
#
# Shared epilogue contract: every wrapper takes optional ``scale``/``bias``
# ((K,) folded-BN vectors) and ``act`` ('relu' | 'relu6' | 'swish' |
# 'sigmoid' | None). On the
# pallas path they are fused into the kernel's output write; on the jnp
# path ``ref.apply_epilogue`` applies the identical math as XLA ops, so the
# two impls stay numerically interchangeable. ``stride`` is call-site
# geometry (only the kernels that support it declare it).

def ilpm(x_padded, w, *, impl="auto", stride=1, block_k=128, scale=None,
         bias=None, act=None):
    if _use_pallas(impl):
        return _il.ilpm_conv(x_padded, w, stride=stride, block_k=block_k,
                             scale=scale, bias=bias, act=act,
                             interpret=_interp())
    return ref.apply_epilogue(ref.ilpm_conv(x_padded, w, stride=stride),
                              scale=scale, bias=bias, act=act)


def direct(x_padded, w, *, impl="auto", stride=1, block_h=8, scale=None,
           bias=None, act=None):
    if _use_pallas(impl):
        return _dc.direct_conv(x_padded, w, stride=stride, block_h=block_h,
                               scale=scale, bias=bias, act=act,
                               interpret=_interp())
    return ref.apply_epilogue(ref.direct_conv(x_padded, w, stride=stride),
                              scale=scale, bias=bias, act=act)


def im2col(x_padded, w, *, impl="auto", scale=None, bias=None, act=None):
    if _use_pallas(impl):
        return _im.im2col_conv(x_padded, w, scale=scale, bias=bias, act=act,
                               interpret=_interp())
    return ref.apply_epilogue(ref.im2col_conv(x_padded, w),
                              scale=scale, bias=bias, act=act)


def libdnn(x_padded, w, *, impl="auto", block_k=128, scale=None, bias=None,
           act=None):
    if _use_pallas(impl):
        return _lib.libdnn_conv(x_padded, w, block_k=block_k, scale=scale,
                                bias=bias, act=act, interpret=_interp())
    return ref.apply_epilogue(ref.libdnn_conv(x_padded, w),
                              scale=scale, bias=bias, act=act)


def winograd(x_padded, w, *, impl="auto", u=None, scale=None, bias=None,
             act=None):
    """``u`` is the cached filter transform U = G g Gᵀ (frozen weights:
    the engine computes it once per plan build)."""
    if _use_pallas(impl):
        return _wg.winograd_conv(x_padded, w, u=u, scale=scale, bias=bias,
                                 act=act, interpret=_interp())
    return ref.apply_epilogue(ref.winograd_conv(x_padded, w, u=u),
                              scale=scale, bias=bias, act=act)


# ---- the grouped family (MobileNet depthwise/pointwise) --------------

def depthwise(x_padded, w, *, impl="auto", stride=1, block_c=128, scale=None,
              bias=None, act=None, pool=False):
    """Depthwise conv: x (B,Hp,Wp,C) pre-padded, w (R,S,1,M·C)
    -> (B,H,W,M·C).

    ``stride`` is geometry, not a tuned parameter — it comes from the call
    site, while ``block_c`` comes from the tuner. Stride 1 and 2 run
    in-kernel (MobileNet downsamples inside depthwise layers); channel
    multipliers M > 1 repeat the input slab on lanes in-kernel. With
    ``pool`` it returns ``(y, sums)``: the output and its (B, M·C) fp32
    spatial sums, a squeeze-and-excitation block's squeeze.
    """
    if _use_pallas(impl):
        return _dw.depthwise_conv(x_padded, w, stride=stride,
                                  block_c=block_c, scale=scale, bias=bias,
                                  act=act, pool=pool, interpret=_interp())
    y = ref.apply_epilogue(ref.depthwise_conv(x_padded, w, stride=stride),
                           scale=scale, bias=bias, act=act)
    return (y, ref.channel_sums(y)) if pool else y


def pointwise(x, w, *, impl="auto", stride=1, block_k=128, scale=None,
              bias=None, act=None, gate=None):
    """1x1 conv: x (B,H,W,C) *unpadded*, w (1,1,C,K) -> (B,H',W',K).
    ``gate`` (B, C) scales the input's channels first (a squeeze-and-
    excitation block's excitation)."""
    if _use_pallas(impl):
        return _pw.pointwise_conv(x, w, stride=stride, block_k=block_k,
                                  scale=scale, bias=bias, act=act, gate=gate,
                                  interpret=_interp())
    if gate is not None:
        x = ref.gate_channels(x, gate)
    return ref.apply_epilogue(ref.pointwise_conv(x, w, stride=stride),
                              scale=scale, bias=bias, act=act)


# ---- fused blocks (per-BLOCK kernels, not per-conv) ------------------
#
# Registered in their own BLOCK_ALGORITHMS table and dispatched through
# ``dispatch_block``: block kernels take a *weights dict* (one entry per
# fused stage) where the per-conv table takes a single filter tensor, so
# sharing ``ALGORITHMS`` would break every caller that iterates it with
# ``dispatch(algo, x, w)`` (the precision sweep, the spy fixtures).

def fused_inverted_residual(x, weights, *, impl="auto", stride=1,
                            block_m=512, residual=False, act="relu6",
                            out_act=None):
    """MobileNet expand->depthwise->project in one kernel launch.

    ``x`` (B,H,W,Cin) *unpadded*; ``weights`` a dict: optional
    ``w1``/``s1``/``b1`` (expansion conv + folded BN — absent for t == 1
    blocks), ``wdw``/``sdw``/``bdw`` (depthwise), ``w2``/``s2``/``b2``
    (projection, linear). ``block_m`` tiles the expanded width (the tuned
    parameter); ``residual`` folds the identity add into the project
    write (stride 1, Cin == Cout only).
    """
    if _use_pallas(impl):
        return _fb.fused_inverted_residual(
            x, weights, stride=stride, block_m=block_m, residual=residual,
            act=act, out_act=out_act, interpret=_interp())
    return ref.fused_inverted_residual(x, weights, stride=stride,
                                       residual=residual, act=act,
                                       out_act=out_act)


def fused_residual_conv(x_padded, weights, *, impl="auto", res,
                        block_k=128, act="relu"):
    """ResNet block tail: the second conv with the shortcut add and outer
    ReLU fused into its output write. ``x_padded`` SAME-padded (stride 1);
    ``weights``: ``w``/``scale``/``bias``; ``res`` the shortcut branch."""
    if _use_pallas(impl):
        return _fb.fused_residual_conv(x_padded, weights, res=res,
                                       block_k=block_k, act=act,
                                       interpret=_interp())
    return ref.fused_residual_conv(x_padded, weights, res=res, act=act)


ALGORITHMS = {"ilpm": ilpm, "direct": direct, "im2col": im2col,
              "libdnn": libdnn, "winograd": winograd,
              "depthwise": depthwise, "pointwise": pointwise}

BLOCK_ALGORITHMS = {"fused_inverted_residual": fused_inverted_residual,
                    "fused_residual_conv": fused_residual_conv}

# the paper's five contenders — interchangeable on any dense 3x3 conv;
# the grouped family (depthwise/pointwise) has its own filter shapes
DENSE_ALGORITHMS = ("ilpm", "direct", "im2col", "libdnn", "winograd")


def kernel_params(algorithm: str, params: dict) -> dict:
    """Keep only the params this algorithm's wrapper accepts.

    It drops the call-site geometry, fused-epilogue and Winograd (``u``)
    keywords a wrapper does not declare: ``stride`` for the stride-1-only
    kernels, say. A wrapper declaring ``**kwargs`` opts out of filtering
    and receives everything (the test suite's spy wrappers rely on this).
    """
    import inspect

    accepted = inspect.signature(ALGORITHMS[algorithm]).parameters
    if any(p.kind is inspect.Parameter.VAR_KEYWORD
           for p in accepted.values()):
        return dict(params)
    return {k: v for k, v in params.items() if k in accepted}


def dispatch(algorithm: str, x_padded, w, *, impl="auto", **params):
    """Run one algorithm by name with its tuned kernel parameters.

    This is the single funnel every planned conv site goes through: the
    engine's jitted forward calls it with the layer's planned algorithm
    name and ``Choice.params``, and runs that kernel; which kernel fits
    a site was decided before (``repro.core.autotune.route``). Semantics:

      * ``ALGORITHMS`` is looked up at *call time*, so tests can spy on
        (or stub out) entries after import;
      * ``params`` are the Choice's tuned knobs plus call-site geometry
        (``stride``), the fused epilogue (``scale``/``bias``/``act`` —
        every conv wrapper accepts these) and the squeeze-and-excitation
        and Winograd operands, filtered per wrapper by ``kernel_params``;
      * ``impl`` selects pallas vs jnp per the module policy above; the
        algorithm itself never changes with ``impl``, only its backend.

    ``x_padded`` must already carry the algorithm's expected padding
    (``pointwise`` takes the raw image; everything else takes SAME-padded
    input — ``repro.core.algorithms.conv2d`` handles this).
    """
    fn = ALGORITHMS[algorithm]
    return fn(x_padded, w, impl=impl, **kernel_params(algorithm, params))


def block_kernel_params(algorithm: str, params: dict) -> dict:
    """``kernel_params`` for the block-level table (same signature-filter
    rule, so spy wrappers declaring ``**kwargs`` opt out identically)."""
    import inspect

    accepted = inspect.signature(BLOCK_ALGORITHMS[algorithm]).parameters
    if any(p.kind is inspect.Parameter.VAR_KEYWORD
           for p in accepted.values()):
        return dict(params)
    return {k: v for k, v in params.items() if k in accepted}


def dispatch_block(algorithm: str, x, weights, *, impl="auto", **params):
    """Block-level twin of ``dispatch``: one call = one fused block.

    The engine's jitted forward funnels every *block* site the plan chose
    to fuse through here (per-conv sites keep going through ``dispatch``).
    ``weights`` is the block's stage dict, ``params`` carries the tuned
    knob (``block_m``/``block_k``) plus call-site geometry
    (``stride``/``residual``/``res``/``act``/``out_act``), filtered per
    algorithm exactly like the per-conv funnel. ``BLOCK_ALGORITHMS`` is
    looked up at call time so the dispatch-spy fixtures can wrap it.
    """
    fn = BLOCK_ALGORITHMS[algorithm]
    return fn(x, weights, impl=impl, **block_kernel_params(algorithm, params))


# ---- 1D ops used by the model substrate ------------------------------

def causal_conv1d(x, w, b=None, *, impl="auto", block_l=512):
    """Depthwise causal conv (Mamba stem): ILP-M technique in 1D."""
    if _use_pallas(impl):
        return _cc.causal_conv1d(x, w, b, block_l=block_l, interpret=_interp())
    return ref.causal_conv1d(x, w, b)


def conv1d_dense(x, w, b=None, *, stride=1):
    return ref.conv1d_dense(x, w, b, stride=stride)
