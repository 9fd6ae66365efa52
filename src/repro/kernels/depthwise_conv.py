"""Depthwise convolution — the MobileNet-family workhorse, ILP-M style.

Depthwise layers dominate mobile inference time (Zhang et al. 2020) and are
pure VPU work on TPU: each channel convolves only itself, so there is no
C-contraction to feed the MXU. The ILP-M blocking transfers directly:

  * channels C on the LANE dimension (the paper maps threads -> output
    channels; depthwise output channels == input channels);
  * the (padded) image tile is **VMEM-resident across the whole grid row**
    — its channel slab's index map ignores nothing it doesn't have to, and
    each grid step owns a `block_c` channel slab end-to-end (image slab,
    filter slab, output slab all cut on the same axis), so nothing is
    refetched;
  * static tap loop: each (r, s) step is one window load times one
    per-channel filter row, `H·W : 1` arithmetic:load on the filter operand
    — the paper's `workgroup_size : 1` ratio, elementwise instead of MXU.

Stride 1 and 2 both run in-kernel (MobileNet downsamples inside its
depthwise layers): the slab is held in its stride phases
(``phases.split``), so every tap window is a contiguous slice. Channel
multipliers > 1 are supported with lax's HWIO convention — filters
(R, S, 1, M·C), output channel k reading input channel k // M — by
repeating the input slab M× on lanes inside the kernel. An
optional (scale, bias, act) epilogue folds BN + ReLU6 into the output
write, same contract as the dense kernels.

With ``pool=True`` (a squeeze-and-excitation block's depthwise conv) the
epilogue also writes the slab's per-channel spatial sums, in fp32, as a
second output: each grid step holds one channel slab of the whole image,
so it holds every term of those sums. That variant is a pallas_call of
its own name, ``depthwise_pool``, so a device trace tells it apart.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import phases
from repro.kernels.fusion import epilogue_operands
from repro.kernels.ref import apply_act


def _kernel(x_ref, w_ref, *refs, H, W, R, S, stride, mult, act, fused,
            pool):
    """x_ref: (1, stride², Hq, Wq, TC) padded image channel slab in its
    stride phases, VMEM-pinned.
    w_ref: (R, S, 1, TK) — the slab's per-channel filter taps (TK = M·TC).
    refs: optional (scale, bias) (1, TK) slabs, then o_ref (1, H, W, TK),
    then with ``pool`` s_ref (1, 1, TK), the slab's spatial sums.
    """
    o_ref = refs[-2] if pool else refs[-1]
    TK = w_ref.shape[-1]
    acc = jnp.zeros((H, W, TK), jnp.float32)
    for r in range(R):          # static taps — fully unrolled, VPU-pipelined
        for s in range(S):
            ph, dr, dc = phases.tap(r, s, stride)
            xs = x_ref[0, ph, dr:dr + H, dc:dc + W, :]
            if mult > 1:        # channel k convolves input channel k // M
                xs = jnp.repeat(xs, mult, axis=-1)
            acc += xs.astype(jnp.float32) * w_ref[r, s, 0].astype(jnp.float32)
    if fused:
        acc = acc * refs[0][0] + refs[1][0]
    acc = apply_act(acc, act)
    o_ref[0] = acc.astype(o_ref.dtype)
    if pool:
        refs[-1][0] = acc.sum(axis=0).sum(axis=0, keepdims=True)


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_c", "act", "pool",
                                    "interpret"))
def depthwise_conv(x_padded, w, *, stride: int = 1, block_c: int = 128,
                   scale=None, bias=None, act=None, pool: bool = False,
                   interpret: bool = False):
    """x_padded: (B, Hp, Wp, C) pre-padded; w: (R, S, 1, M·C)
    -> (B, H, W, M·C), and with ``pool`` also its (B, M·C) fp32 spatial
    sums, taken after the epilogue.

    ``block_c`` tiles the *output*-channel axis (the tuned kernel
    parameter); the grid is (batch, channel blocks) and every operand of
    one grid step is the same channel slab — for multiplier M the image
    slab carries ``block_c // M`` input channels feeding ``block_c``
    output lanes.
    """
    B, Hp, Wp, C = x_padded.shape
    R, S, cg, K = w.shape
    assert cg == 1 and K % C == 0, (
        f"depthwise kernel wants (R,S,1,M*C) filters for C={C}, got {w.shape}")
    mult = K // C
    H = (Hp - R) // stride + 1
    W = (Wp - S) // stride + 1
    tk = min(block_c, K)
    tk = max(mult, tk - tk % mult)  # output slab must hold whole input lanes
    tc = tk // mult
    grid = (B, pl.cdiv(K, tk))
    xq = phases.split(x_padded, stride)
    operands = [xq, w]
    in_specs = [
        pl.BlockSpec((1,) + xq.shape[1:4] + (tc,),
                     lambda b, c: (b, 0, 0, 0, c)),
        pl.BlockSpec((R, S, 1, tk), lambda b, c: (0, 0, 0, c)),
    ]
    fused, extra, extra_specs = epilogue_operands(
        scale, bias, K, tk, lambda b, c: (0, c))
    operands += extra
    in_specs += extra_specs
    out_specs = pl.BlockSpec((1, H, W, tk), lambda b, c: (b, 0, 0, c))
    out_shape = jax.ShapeDtypeStruct((B, H, W, K), x_padded.dtype)
    if pool:
        out_specs = (out_specs,
                     pl.BlockSpec((1, 1, tk), lambda b, c: (b, 0, c)))
        out_shape = (out_shape,
                     jax.ShapeDtypeStruct((B, 1, K), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_kernel, H=H, W=W, R=R, S=S, stride=stride,
                          mult=mult, act=act, fused=fused, pool=pool),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        name="depthwise_pool" if pool else None,
    )(*operands)
    if pool:
        y, sums = out
        return y, sums[:, 0]
    return out
