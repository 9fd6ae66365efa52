"""Stride-phase layout: how the strided kernels read their tap windows.

Mosaic (the TPU kernel compiler) lowers a strided read of a VMEM ref only
when the ref's lane (last) dim is at most 128 wide, and a strided slice of
a loaded value not at all (it becomes a gather). So no kernel reads with a
stride. The wrapper splits the padded image into its ``stride**2``
phases — phase ``(p, q)`` holds ``x[p::stride, q::stride]`` — and tap
``(r, s)`` of a stride-``stride`` conv becomes a contiguous window of phase
``(r % stride, s % stride)`` at offset ``(r // stride, s // stride)``.

At stride 1 the split is a free reshape (one phase); at stride 2 it is one
XLA transpose pass over the kernel's input.

Where the phases of a thin image fit in one 128-lane tile together (an RGB
stem: 4 phases x 3 channels), ``fold`` moves them into the channel axis
instead (space-to-depth), turning an RxS/stride conv into a
ceil(R/stride) x ceil(S/stride) stride-1 conv: 16 taps instead of 49 for
the 7x7/2 ResNet stem. A tap over 12 lanes costs the kernel what a tap
over 3 does, so the stem compiles and runs about three times fewer taps.
"""
from __future__ import annotations

import jax.numpy as jnp


def split(x, stride: int):
    """(B, H, W, C) -> (B, stride**2, ceil(H/stride), ceil(W/stride), C).

    Phase ``p * stride + q`` holds ``x[:, p::stride, q::stride]``; where
    ``stride`` does not divide H or W, the shorter phases are zero-padded
    at their end to the common size (kernels never read those rows)."""
    if stride == 1:
        return x[:, None]
    B, H, W, C = x.shape
    hq, wq = -(-H // stride), -(-W // stride)
    x = jnp.pad(x, ((0, 0), (0, hq * stride - H), (0, wq * stride - W),
                    (0, 0)))
    x = x.reshape(B, hq, stride, wq, stride, C).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(B, stride * stride, hq, wq, C)


def tap(r: int, s: int, stride: int) -> tuple[int, int, int]:
    """(phase, row offset, col offset) of tap ``(r, s)`` in ``split``'s
    layout: the tap's window is ``phases[phase, dr:dr + H, dc:dc + W]``."""
    return (r % stride) * stride + s % stride, r // stride, s // stride


LANES = 128  # a TPU vreg's lane width


def foldable(c: int, stride: int) -> bool:
    """Whether ``fold`` applies: a strided conv whose stride**2 phases of
    ``c`` channels fit in one lane tile together."""
    return stride > 1 and stride * stride * c <= LANES


def fold(x_padded, w, stride: int):
    """Space-to-depth a strided conv into a stride-1 one.

    x_padded: (B, (H-1)*stride+R, (W-1)*stride+S, C); w: (R, S, C, K)
    -> ((B, H+R'-1, W+S'-1, stride²·C), (R', S', stride²·C, K)) with
    R' = ceil(R/stride): the stride-1 conv of the pair equals the strided
    conv of the inputs. Filter taps past R (or S) are zeros."""
    B, Hp, Wp, C = x_padded.shape
    R, S, _, K = w.shape
    rq, sq = -(-R // stride), -(-S // stride)
    H, W = (Hp - R) // stride + 1, (Wp - S) // stride + 1
    xq = split(x_padded, stride)[:, :, :H + rq - 1, :W + sq - 1]
    xq = xq.transpose(0, 2, 3, 1, 4).reshape(B, H + rq - 1, W + sq - 1, -1)
    wq = jnp.pad(w, ((0, rq * stride - R), (0, sq * stride - S), (0, 0),
                     (0, 0)))
    wq = wq.reshape(rq, stride, sq, stride, C, K).transpose(0, 2, 1, 3, 4, 5)
    return xq, wq.reshape(rq, sq, stride * stride * C, K)
