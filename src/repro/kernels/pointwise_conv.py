"""Pointwise (1x1) convolution — the other half of the MobileNet family.

A 1x1 conv is a single (pixels, C) @ (C, K) GEMM; the ILP-M mapping is the
degenerate one-tap case of `ilpm_conv`:

  * output channels K on the LANE dimension, K-tiled grid;
  * the image tile is **VMEM-resident across the whole grid row** (its
    BlockSpec index map ignores the K axis) — expand/project pairs in
    inverted-residual blocks reread the same activations, so residency is
    where the traffic win is;
  * one MXU contraction per grid step, no halo and no padding (R=S=1);
  * stride ∈ {1, 2}: strided 1x1 convs (ResNet projection shortcuts at
    stage entries) read only every stride-th pixel, subsampled by the
    wrapper (one XLA strided slice): Mosaic cannot lower a strided read of
    a tile wider than 128 lanes (see ``phases``);
  * optional fused (scale, bias, act) epilogue in the output write, same
    contract as `ilpm_conv`;
  * optional ``gate`` (B, C): each input channel scaled by its gate (in
    fp32) before the MXU product — the excitation of a squeeze-and-
    excitation block, applied where its projection reads the tile. That
    variant is a pallas_call of its own name, ``pointwise_gated``, so a
    device trace tells it apart.

Kept separate from `ilpm` so the tuner can cost it without tap-loop
overheads and so dispatch can skip SAME padding entirely.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fusion import epilogue_operands
from repro.kernels.ref import apply_act


def _kernel(x_ref, w_ref, *refs, H, W, act, fused, gated):
    """x_ref: (1, H, W, C) — full (subsampled) image, VMEM-pinned.
    w_ref: (1, 1, C, TK) — one output-channel slab.
    refs: optional (scale, bias) (1, TK) slabs, then with ``gated`` the
    image's (1, 1, C) gate, then o_ref (1, H, W, TK).
    """
    o_ref = refs[-1]
    C = x_ref.shape[-1]
    TK = w_ref.shape[-1]
    xs = x_ref[0].reshape(H * W, C)
    if gated:
        xs = (xs.astype(jnp.float32) * refs[-2][0]).astype(xs.dtype)
    acc = jnp.dot(xs, w_ref[0, 0], preferred_element_type=jnp.float32)
    if fused:
        acc = acc * refs[0][0] + refs[1][0]
    acc = apply_act(acc, act)
    o_ref[0] = acc.reshape(H, W, TK).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_k", "act", "interpret"))
def pointwise_conv(x, w, *, stride: int = 1, block_k: int = 128,
                   scale=None, bias=None, act=None, gate=None,
                   interpret: bool = False):
    """x: (B, H, W, C) — no padding needed; w: (1,1,C,K)
    -> (B, ceil(H/stride), ceil(W/stride), K). ``gate`` (B, C) scales the
    input's channels first."""
    R, S, _, K = w.shape
    assert (R, S) == (1, 1), f"pointwise kernel wants 1x1 filters, got {w.shape}"
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    B, Ho, Wo, C = x.shape
    tk = min(block_k, K)
    grid = (B, pl.cdiv(K, tk))
    operands = [x, w]
    in_specs = [
        # index map ignores k -> image stays resident across the K row
        pl.BlockSpec((1, Ho, Wo, C), lambda b, k: (b, 0, 0, 0)),
        pl.BlockSpec((1, 1, C, tk), lambda b, k: (0, 0, 0, k)),
    ]
    fused, extra, extra_specs = epilogue_operands(
        scale, bias, K, tk, lambda b, k: (0, k))
    operands += extra
    in_specs += extra_specs
    gated = gate is not None
    if gated:
        operands.append(gate.astype(jnp.float32).reshape(B, 1, C))
        in_specs.append(pl.BlockSpec((1, 1, C), lambda b, k: (b, 0, 0)))
    return pl.pallas_call(
        functools.partial(_kernel, H=Ho, W=Wo, act=act, fused=fused,
                          gated=gated),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Ho, Wo, tk), lambda b, k: (b, 0, 0, k)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, K), x.dtype),
        interpret=interpret,
        name="pointwise_gated" if gated else None,
    )(*operands)
