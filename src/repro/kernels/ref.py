"""Pure-jnp oracles for every kernel.

``conv2d_reference`` (lax.conv_general_dilated) is the ground truth; each
algorithm also has a structural reference that mirrors its data movement in
plain jnp (patches for im2col, tap loop for ilpm, Winograd transforms) so the
Pallas kernels can be checked against the *algorithm*, and every algorithm
against the ground truth.

Layouts: activations NHWC, filters HWIO (R, S, C, K) — the TPU adaptation of
the paper's [C][R][S][K] coalesced layout (K minor => lane-aligned).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def conv2d_reference(x, w, *, stride=1, padding="SAME", groups=1):
    """Ground truth. x: (B,H,W,C), w: (R,S,C/groups,K).

    ``groups`` is lax's ``feature_group_count``; ``groups == C == K`` is a
    depthwise conv with weights (R, S, 1, C).
    """
    return jax.lax.conv_general_dilated(
        x, w, window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups)


def apply_act(y, act):
    """Apply a named activation ('relu' | 'relu6' | 'swish' | 'sigmoid' |
    None).

    Shared by the Pallas kernels' fused epilogues (it is plain jnp, so it
    traces inside a kernel body) and the jnp reference paths. Swish is
    x·sigmoid(x), EfficientNet's activation; sigmoid is its SE gate's.
    """
    if act is None:
        return y
    if act == "relu":
        return jnp.maximum(y, 0)
    if act == "relu6":
        return jnp.clip(y, 0.0, 6.0)
    if act == "swish":
        return y * jax.nn.sigmoid(y)
    if act == "sigmoid":
        return jax.nn.sigmoid(y)
    raise ValueError(f"unknown activation {act!r}")


def channel_sums(y):
    """(B, H, W, C) -> (B, C) fp32 spatial sums: the squeeze of a
    squeeze-and-excitation gate, before its division by H·W."""
    return y.astype(jnp.float32).sum(axis=(1, 2))


def gate_channels(x, gate):
    """Scale each channel of (B, H, W, C) ``x`` by the (B, C) ``gate`` in
    fp32, cast back to the compute dtype: the excitation of a
    squeeze-and-excitation block, as the gated pointwise kernel applies it
    to its input tile."""
    z = x.astype(jnp.float32) * gate.astype(jnp.float32)[:, None, None, :]
    return z.astype(x.dtype)


def apply_epilogue(y, scale=None, bias=None, act=None):
    """Unfused conv epilogue: y*scale + bias then activation, in fp32.

    The jnp/XLA counterpart of the kernels' in-kernel epilogue — used by
    the `impl='jnp'` wrappers and the XLA escape hatch so fused and
    unfused paths compute the same function.
    """
    if scale is None and bias is None and act is None:
        return y
    z = y.astype(jnp.float32)
    if scale is not None:
        z = z * scale.astype(jnp.float32)
    if bias is not None:
        z = z + bias.astype(jnp.float32)
    return apply_act(z, act).astype(y.dtype)


def pad_same(x, r, s, stride=1):
    """Explicit SAME padding so kernels see pre-padded inputs.

    Matches XLA's SAME convention: total pad (out-1)*stride + r - h split
    low-first; stride-1 reduces to the familiar symmetric (r-1)//2 halo.
    """
    h, w = x.shape[1], x.shape[2]
    ph = max((-(-h // stride) - 1) * stride + r - h, 0)
    pw = max((-(-w // stride) - 1) * stride + s - w, 0)
    return jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                       (pw // 2, pw - pw // 2), (0, 0)))


# ----------------------------------------------------------------------
# ILP-M: tap-major accumulation, image resident, K vectorized


def ilpm_conv(x_padded, w, *, stride=1):
    """x_padded: (B, (H-1)*stride+R, (W-1)*stride+S, C); w: (R,S,C,K)
    -> (B,H,W,K).

    The algorithm's structure in jnp: static loop over taps, each tap a
    (pixels, C) @ (C, K) contraction — one weight slab per step amortized
    over the whole image tile (the paper's workgroup_size:1 ratio). Strided
    taps are strided windows of the same resident image.
    """
    R, S, C, K = w.shape
    B, Hp, Wp, _ = x_padded.shape
    H = (Hp - R) // stride + 1
    W = (Wp - S) // stride + 1
    acc = jnp.zeros((B, H * W, K), jnp.float32)
    for r in range(R):
        for s in range(S):
            xs = x_padded[:, r:r + (H - 1) * stride + 1:stride,
                          s:s + (W - 1) * stride + 1:stride, :].reshape(
                              B, H * W, C)
            acc = acc + jnp.einsum("bpc,ck->bpk", xs, w[r, s],
                                   preferred_element_type=jnp.float32)
    return acc.reshape(B, H, W, K).astype(x_padded.dtype)


# ----------------------------------------------------------------------
# direct: pixel-major, full filter set resident


def direct_conv(x_padded, w, *, stride=1):
    """Same math, pixel-tile grid ordering; kept numerically identical —
    the structural difference (filter-set residency) is a kernel concern."""
    R, S, C, K = w.shape
    B, Hp, Wp, _ = x_padded.shape
    H = (Hp - R) // stride + 1
    W = (Wp - S) // stride + 1
    # gather taps then one big contraction per pixel tile (filters stationary)
    taps = jnp.stack([x_padded[:, r:r + (H - 1) * stride + 1:stride,
                               s:s + (W - 1) * stride + 1:stride, :]
                      for r in range(R) for s in range(S)], axis=-2)
    return jnp.einsum("bhwtc,tck->bhwk", taps, w.reshape(R * S, C, K),
                      preferred_element_type=jnp.float32).astype(x_padded.dtype)


# ----------------------------------------------------------------------
# im2col: materialized patch matrix + GEMM (two phases)


def im2col_unroll(x_padded, r, s):
    """-> (B, H*W, R*S*C): the unrolled input matrix (HBM round-trip)."""
    R, S = r, s
    B, Hp, Wp, C = x_padded.shape
    H, W = Hp - R + 1, Wp - S + 1
    cols = [x_padded[:, i:i + H, j:j + W, :]
            for i in range(R) for j in range(S)]
    return jnp.concatenate(cols, axis=-1).reshape(B, H * W, R * S * C)


def im2col_conv(x_padded, w):
    R, S, C, K = w.shape
    B, Hp, Wp, _ = x_padded.shape
    H, W = Hp - R + 1, Wp - S + 1
    patches = im2col_unroll(x_padded, R, S)
    out = jnp.einsum("bpc,ck->bpk", patches, w.reshape(R * S * C, K),
                     preferred_element_type=jnp.float32)
    return out.reshape(B, H, W, K).astype(x_padded.dtype)


# libdnn = fused im2col (identical math; fusion is a kernel concern)
libdnn_conv = im2col_conv


# ----------------------------------------------------------------------
# Winograd F(2x2, 3x3)

_BT = np.array([[1, 0, -1, 0],
                [0, 1, 1, 0],
                [0, -1, 1, 0],
                [0, 1, 0, -1]], np.float32)
_G = np.array([[1, 0, 0],
               [0.5, 0.5, 0.5],
               [0.5, -0.5, 0.5],
               [0, 0, 1]], np.float32)
_AT = np.array([[1, 1, 1, 0],
                [0, 1, -1, -1]], np.float32)


def winograd_filter_transform(w):
    """(3,3,C,K) -> U (4,4,C,K). Constant at inference (paper §5.2)."""
    return jnp.einsum("ar,rsck,bs->abck", _G, w, _G)


def winograd_input_transform(x_padded, H, W):
    """Tile into 4x4 patches (stride 2) and apply B^T d B.

    -> V: (B, 4, 4, nt, C) with nt = (H/2)*(W/2) output tiles.
    """
    Bsz, Hp, Wp, C = x_padded.shape
    th, tw = H // 2, W // 2
    # gather 4x4 windows at stride 2
    d = jnp.stack([x_padded[:, 2 * i:2 * i + 4] for i in range(th)], axis=1)
    d = jnp.stack([d[:, :, :, 2 * j:2 * j + 4] for j in range(tw)], axis=2)
    # d: (B, th, tw, 4, 4, C)
    v = jnp.einsum("ar,bijrsc,ds->bijadc", _BT, d, _BT)
    return v.transpose(0, 3, 4, 1, 2, 5).reshape(Bsz, 4, 4, th * tw, C)


def winograd_output_transform(m, H, W):
    """m: (B,4,4,nt,K) -> (B,H,W,K) via A^T m A + tile scatter."""
    Bsz = m.shape[0]
    K = m.shape[-1]
    th, tw = H // 2, W // 2
    y = jnp.einsum("ar,brstk,ds->btadk", _AT, m, _AT)  # (B, nt, 2, 2, K)
    y = y.reshape(Bsz, th, tw, 2, 2, K).transpose(0, 1, 3, 2, 4, 5)
    return y.reshape(Bsz, H, W, K)


def winograd_conv(x_padded, w, *, u=None):
    """Full F(2x2,3x3) pipeline; requires even H, W. ``u`` optionally
    carries the precomputed filter transform (frozen at inference)."""
    R, S, C, K = w.shape
    assert (R, S) == (3, 3), "winograd F(2,3) is 3x3-only"
    B, Hp, Wp, _ = x_padded.shape
    H, W = Hp - 2, Wp - 2
    assert H % 2 == 0 and W % 2 == 0, "even output dims required"
    if u is None:
        u = winograd_filter_transform(w)                  # (4,4,C,K)
    v = winograd_input_transform(x_padded, H, W)          # (B,4,4,nt,C)
    m = jnp.einsum("bxytc,xyck->bxytk", v, u,
                   preferred_element_type=jnp.float32)    # 16 batched GEMMs
    return winograd_output_transform(m.astype(x_padded.dtype), H, W)


# ----------------------------------------------------------------------
# depthwise / pointwise (the MobileNet family factorization)


def depthwise_conv(x_padded, w, *, stride=1):
    """x_padded: (B, Hp, Wp, C) pre-padded; w: (R, S, 1, M*C)
    -> (B, H, W, M*C).

    The algorithm's structure in jnp: static tap loop, each tap a strided
    window of the resident image scaled by one per-channel filter row — all
    VPU work, no contraction (each channel convolves only itself). Channel
    multipliers M > 1 follow lax's HWIO convention: output channel k reads
    input channel k // M.
    """
    R, S, _, K = w.shape
    B, Hp, Wp, C = x_padded.shape
    assert K % C == 0, (w.shape, x_padded.shape)
    mult = K // C
    H = (Hp - R) // stride + 1
    W = (Wp - S) // stride + 1
    acc = jnp.zeros((B, H, W, K), jnp.float32)
    for r in range(R):
        for s in range(S):
            xs = x_padded[:, r:r + (H - 1) * stride + 1:stride,
                          s:s + (W - 1) * stride + 1:stride, :]
            if mult > 1:
                xs = jnp.repeat(xs, mult, axis=-1)
            acc = acc + xs.astype(jnp.float32) * w[r, s, 0].astype(jnp.float32)
    return acc.astype(x_padded.dtype)


def pointwise_conv(x, w, *, stride=1):
    """x: (B, H, W, C); w: (1, 1, C, K) -> (B, ceil(H/s), ceil(W/s), K).

    A 1x1 conv is one (pixels, C) @ (C, K) GEMM — no padding, no taps; a
    strided 1x1 (ResNet projection shortcut) just subsamples first."""
    if stride != 1:
        x = x[:, ::stride, ::stride, :]
    return jnp.einsum("bhwc,ck->bhwk", x, w[0, 0],
                      preferred_element_type=jnp.float32).astype(x.dtype)


# ----------------------------------------------------------------------
# fused blocks: the per-layer chains the megakernels replace, composed
# stage for stage from the references above (same casts, same op order —
# these ARE the per-layer semantics, so fused-vs-composed parity checks
# the fusion and nothing else)


def fused_inverted_residual(x, weights, *, stride=1, residual=False,
                            act="relu6", out_act=None):
    """Composed per-layer reference of the inverted-residual megakernel:
    expand (1x1 + BN/act) -> SAME pad -> depthwise (+ BN/act) -> project
    (1x1 + BN, linear) -> optional identity add. ``weights`` as in
    ``fused_block.fused_inverted_residual``; each stage's epilogue runs
    in fp32 and casts back to the compute dtype, exactly like the
    per-layer kernels' output writes."""
    h = x
    if weights.get("w1") is not None:
        h = apply_epilogue(pointwise_conv(h, weights["w1"]),
                           weights.get("s1"), weights.get("b1"), act)
    wdw = weights["wdw"]
    h = pad_same(h, wdw.shape[0], wdw.shape[1], stride)
    h = apply_epilogue(depthwise_conv(h, wdw, stride=stride),
                       weights.get("sdw"), weights.get("bdw"), act)
    h = apply_epilogue(pointwise_conv(h, weights["w2"]),
                       weights.get("s2"), weights.get("b2"), out_act)
    if residual:
        h = h + x
    return h


def fused_residual_conv(x_padded, weights, *, res, act="relu"):
    """Composed per-layer reference of the residual-conv megakernel: the
    conv + folded BN writes at the compute dtype, then the shortcut add
    and outer activation run as a separate (per-layer: extra HBM pass)
    step in the compute dtype."""
    h = apply_epilogue(ilpm_conv(x_padded, weights["w"]),
                       weights.get("scale"), weights.get("bias"), None)
    return apply_act(h + res, act)


# ----------------------------------------------------------------------
# depthwise causal conv1d (Mamba stem) — the paper's technique in 1D


def causal_conv1d(x, w, b=None):
    """x: (B, L, C); w: (k, C) depthwise; left-padded (causal)."""
    k = w.shape[0]
    acc = jnp.zeros(x.shape, jnp.float32)
    for j in range(k):
        shift = k - 1 - j
        xs = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :x.shape[1]]
        acc = acc + xs.astype(jnp.float32) * w[j].astype(jnp.float32)
    if b is not None:
        acc = acc + b.astype(jnp.float32)
    return acc.astype(x.dtype)


def conv1d_dense(x, w, b=None, *, stride=1):
    """x: (B,L,Cin); w: (k,Cin,Cout) dense 1D conv, SAME padding."""
    k = w.shape[0]
    y = jax.lax.conv_general_dilated(
        x[:, :, None, :], w[:, None], window_strides=(stride, 1),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"))[:, :, 0]
    if b is not None:
        y = y + b
    return y


def gemm(a, b):
    return jnp.dot(a, b, preferred_element_type=jnp.float32).astype(a.dtype)
