"""ILP-M convolution — the paper's contribution, as a Pallas TPU kernel.

TPU adaptation of the algorithm (DESIGN.md §2):
  * output channels K on the LANE dimension (the paper maps threads -> K);
  * the (padded) input image tile is **VMEM-resident across the whole grid
    row** — its BlockSpec index map ignores the K grid axis, so Pallas keeps
    it on-chip and never refetches it (the paper's shared-memory image tile,
    minus the barrier);
  * filters in HWIO ([R][S][C][K], K minor) — the paper's [C][R][S][K]
    coalesced layout, lane-aligned on TPU;
  * static tap loop: each (r, s) step is one `(H·W, C) @ (C, K_blk)` MXU
    contraction — one weight slab amortized over every pixel of the tile,
    the `workgroup_size : 1` arithmetic:load ratio of the paper;
  * stride ∈ {1, 2}: the resident image is held in its stride phases
    (``phases.split``), so every tap window is a contiguous slice and
    strided layers (the ResNet stem's 7×7/2, stage-entry 3×3/2) keep the
    same image-residency structure instead of escaping to XLA; a thin
    strided input (the RGB stems) is folded into its channels instead
    (``phases.fold``), so it runs as a stride-1 conv with fewer taps;
  * optional fused epilogue: folded-BN `y*scale + bias` and ReLU/ReLU6
    applied to the accumulator before the single output write — the
    conv+BN+act triple costs one HBM pass instead of three.

Single-image (B small) is the design premise, exactly as in the paper: the
pixel axis, not the batch axis, feeds the sublanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import phases
from repro.kernels.fusion import epilogue_operands
from repro.kernels.ref import apply_act


def _kernel(x_ref, w_ref, *refs, H, W, R, S, stride, act, fused):
    """x_ref: (1, stride², Hq, Wq, C) — the full padded image in its
    stride phases, VMEM-pinned.
    w_ref: (R, S, C, TK) — one output-channel slab.
    refs: optional (scale, bias) (1, TK) slabs, then o_ref (1, H, W, TK).
    """
    o_ref = refs[-1]
    C = x_ref.shape[-1]
    TK = w_ref.shape[-1]
    acc = jnp.zeros((H * W, TK), jnp.float32)
    for r in range(R):          # static taps — fully unrolled, MXU-pipelined
        for s in range(S):
            ph, dr, dc = phases.tap(r, s, stride)
            xs = x_ref[0, ph, dr:dr + H, dc:dc + W, :].reshape(H * W, C)
            acc += jnp.dot(xs, w_ref[r, s],
                           preferred_element_type=jnp.float32)
    if fused:
        acc = acc * refs[0][0] + refs[1][0]
    acc = apply_act(acc, act)
    o_ref[0] = acc.reshape(H, W, TK).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("stride", "block_k", "act", "interpret"))
def ilpm_conv(x_padded, w, *, stride: int = 1, block_k: int = 128,
              scale=None, bias=None, act=None, interpret: bool = False):
    """x_padded: (B, (H-1)*stride+R, (W-1)*stride+S, C) pre-padded;
    w: (R,S,C,K) -> (B,H,W,K).

    ``scale``/``bias`` are optional (K,) folded-BN vectors and ``act`` an
    optional activation name ('relu' | 'relu6'), all applied inside the
    kernel's output write.
    """
    if phases.foldable(x_padded.shape[-1], stride):
        x_padded, w = phases.fold(x_padded, w, stride)
        stride = 1
    B, Hp, Wp, C = x_padded.shape
    R, S, _, K = w.shape
    H = (Hp - R) // stride + 1
    W = (Wp - S) // stride + 1
    tk = min(block_k, K)
    grid = (B, pl.cdiv(K, tk))
    xq = phases.split(x_padded, stride)
    operands = [xq, w]
    in_specs = [
        # index map ignores k -> image stays resident across the K row
        pl.BlockSpec((1,) + xq.shape[1:], lambda b, k: (b, 0, 0, 0, 0)),
        pl.BlockSpec((R, S, C, tk), lambda b, k: (0, 0, 0, k)),
    ]
    fused, extra, extra_specs = epilogue_operands(
        scale, bias, K, tk, lambda b, k: (0, k))
    operands += extra
    in_specs += extra_specs
    return pl.pallas_call(
        functools.partial(_kernel, H=H, W=W, R=R, S=S, stride=stride,
                          act=act, fused=fused),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, W, tk), lambda b, k: (b, 0, 0, k)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, K), x_padded.dtype),
        interpret=interpret,
    )(*operands)
