"""Fused-block megakernels — the per-block speed tier above per-conv.

The paper's lesson is that single-image inference is memory-bound, so the
win is cutting HBM round-trips. The per-conv kernels (ilpm/depthwise/
pointwise) already keep each layer's image VMEM-resident; these kernels
keep the *intermediates between layers* resident too:

  * ``fused_inverted_residual`` — MobileNet's expand(1x1) -> depthwise
    (RxS, stride 1|2) -> project(1x1) chain in ONE ``pallas_call``. The
    input arrives in its stride phases (``phases.split``), so at stride 2
    every depthwise tap is a contiguous window of one expanded phase, as
    in the per-layer strided kernels. The expanded tensor (t*Cin wide —
    the largest activation in the network) is computed, SAME-padded,
    convolved, and consumed entirely in VMEM;
    it never touches HBM ("High Performance Depthwise and Pointwise
    Convolutions on Mobile Devices" builds its mobile speedup on exactly
    this fusion). The expanded width is cut into per-channel slabs
    (``block_m``): the grid walks (batch, mid-slab), each step expands one
    slab, depthwise-convolves it, and accumulates its partial projection
    into an fp32 VMEM scratch; the last slab applies the project BN
    epilogue and — when ``residual`` (stride 1, Cin == Cout) — folds the
    identity add into the single output write, reusing the already-
    resident input (the shortcut costs zero extra HBM traffic).
  * ``fused_residual_conv`` — the second conv of a ResNet basic/
    bottleneck block (ilpm-style tap loop, K on lanes) with the shortcut
    add and the outer ReLU folded into the output write: per-layer this
    costs a full extra read-modify-write pass over the conv output.

Numerics mirror the per-layer chain stage for stage — fp32 accumulate,
each stage's BN/act epilogue in fp32, cast to the compute dtype exactly
where the per-layer kernel's output write casts — so at fp32 the fused
block with a single mid slab is *bitwise* equal to the per-layer path
(the project contraction is split only when ``block_m < mid``, which
reorders the reduction; ``block_m`` defaults large so single-slab wins
whenever it fits VMEM).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import phases
from repro.kernels.ref import apply_act


def _vec(v, n):
    """Materialize an optional (n,) epilogue vector as a (1, n) fp32 row
    (ones for a missing scale, zeros for a missing bias are handled by the
    caller passing None through ``_vec_or``)."""
    return v.astype(jnp.float32).reshape(1, n)


def _vec_or(v, n, fill):
    if v is None:
        return (jnp.ones((1, n), jnp.float32) if fill == 1.0
                else jnp.zeros((1, n), jnp.float32))
    return _vec(v, n)


# ----------------------------------------------------------------------
# inverted residual: expand -> depthwise -> project, expanded tensor in VMEM


def _ir_kernel(x_ref, *refs, H, W, OH, OW, R, S, stride, pads, act, out_act,
               residual, expanded, nm, compute_dtype):
    """One grid step = one expanded-channel slab of one image.

    x_ref: (1, stride², Hx, Wx, Cin) — the *unpadded* input in its stride
    phases (``phases.split``), VMEM-resident across the whole mid-slab
    row (its index map ignores the m axis); at stride 1 also the residual
    identity. Then, when ``expanded``: w1 (1,1,Cin,TM), s1/b1 (1,TM);
    always: wdw (R,S,1,TM), sdw/bdw (1,TM), w2 (1,1,TM,Cout), s2/b2
    (1,Cout), o_ref (1,OH,OW,Cout), and the fp32 (OH*OW, Cout) projection
    accumulator scratch.
    """
    acc_ref = refs[-1]
    o_ref = refs[-2]
    if expanded:
        w1, s1, b1, wdw, sdw, bdw, w2, s2, b2 = refs[:9]
    else:
        wdw, sdw, bdw, w2, s2, b2 = refs[:6]
    m = pl.program_id(1)
    x = x_ref[0]
    # --- expand: one (pixels, Cin) @ (Cin, TM) MXU step over every phase
    # + BN/act epilogue, cast to the compute dtype exactly where the
    # per-layer pointwise kernel's output write would cast (a 1x1 conv is
    # per-pixel, so expanding the phases is expanding the image) ---
    if expanded:
        e = jnp.dot(x.reshape(-1, x.shape[-1]), w1[0, 0],
                    preferred_element_type=jnp.float32)
        e = apply_act(e * s1[0] + b1[0], act).astype(compute_dtype)
        e = e.reshape(x.shape[:-1] + e.shape[-1:])
    else:
        e = x  # t == 1: the slab *is* the input (tm == mid == cin)
    # each phase, less the rows and cols split() padded it with
    e = {(p, q): e[p * stride + q, :-(-(H - p) // stride),
                   :-(-(W - q) // stride)]
         for p in range(stride) for q in range(stride)}
    # --- SAME-pad in VMEM: exact zeros, identical to the per-layer
    # pad_same of the expand output (the expanded tensor's HBM round-trip
    # this kernel exists to delete). Phase (a, b) of the padded slab is
    # input phase ((a - top) % stride, (b - left) % stride) shifted down
    # and right by the rows and columns of padding that land in it. ---
    (top, _), (left, _) = pads
    hq, wq = OH + (R - 1) // stride, OW + (S - 1) // stride

    def padded(a, b):
        src = e[((a - top) % stride, (b - left) % stride)]
        i0 = max(0, -(-(top - a) // stride))
        j0 = max(0, -(-(left - b) // stride))
        n, k = min(src.shape[0], hq - i0), min(src.shape[1], wq - j0)
        return jnp.pad(src[:n, :k], ((i0, hq - i0 - n), (j0, wq - j0 - k),
                                     (0, 0)))

    ep = {(a, b): padded(a, b) for a in range(stride) for b in range(stride)}
    # --- depthwise: static tap loop over the resident padded slab, VPU
    # work, fp32 accumulate, BN/act epilogue, cast-on-"write" (to VMEM) ---
    d = jnp.zeros((OH, OW, wdw.shape[-1]), jnp.float32)
    for r in range(R):
        for s in range(S):
            dr, dc = r // stride, s // stride
            xs = ep[(r % stride, s % stride)][dr:dr + OH, dc:dc + OW, :]
            d += xs.astype(jnp.float32) * wdw[r, s, 0].astype(jnp.float32)
    d = apply_act(d * sdw[0] + bdw[0], act).astype(compute_dtype)
    # --- project: this slab's partial (OH*OW, Cout) contraction ---
    part = jnp.dot(d.reshape(OH * OW, d.shape[-1]), w2[0, 0],
                   preferred_element_type=jnp.float32)

    @pl.when(m == 0)
    def _init():
        acc_ref[...] = part

    @pl.when(m > 0)
    def _accumulate():
        acc_ref[...] += part

    # --- last slab: project epilogue + residual fold + the single write ---
    @pl.when(m == nm - 1)
    def _write():
        y = acc_ref[...] * s2[0] + b2[0]
        y = apply_act(y, out_act).astype(o_ref.dtype)
        if residual:
            # the identity is the already-resident input: zero extra HBM
            y = y + x[0].reshape(y.shape)
        o_ref[0] = y.reshape(OH, OW, y.shape[-1])


@functools.partial(jax.jit, static_argnames=("stride", "block_m", "act",
                                             "out_act", "residual",
                                             "interpret"))
def fused_inverted_residual(x, weights, *, stride: int = 1,
                            block_m: int = 512, residual: bool = False,
                            act: str | None = "relu6",
                            out_act: str | None = None,
                            interpret: bool = False):
    """x: (B, H, W, Cin) *unpadded*; weights: a dict with

      * ``w1`` (1, 1, Cin, mid) + ``s1``/``b1`` (mid,) — the expansion
        conv and its folded BN (omit all three for t == 1 blocks);
      * ``wdw`` (R, S, 1, mid) + ``sdw``/``bdw`` (mid,) — depthwise;
      * ``w2`` (1, 1, mid, Cout) + ``s2``/``b2`` (Cout,) — projection
        (linear: ``out_act`` stays None in MobileNetV2).

    -> (B, ceil(H/stride), ceil(W/stride), Cout). ``block_m`` tiles the
    expanded width (the tuned parameter); slabs must divide ``mid``
    exactly — a non-dividing ``block_m`` falls back to the single-slab
    variant (a ragged mid slab would double-count the projection's
    cross-slab accumulation). ``residual`` folds ``+ x`` into the output
    write (caller guarantees stride == 1 and Cin == Cout).
    """
    B, H, W, Cin = x.shape
    w1 = weights.get("w1")
    expanded = w1 is not None
    wdw, w2 = weights["wdw"], weights["w2"]
    R, S, _, mid = wdw.shape
    Cout = w2.shape[-1]
    assert w2.shape[:3] == (1, 1, mid), w2.shape
    assert not expanded or w1.shape == (1, 1, Cin, mid), (w1.shape, mid)
    assert expanded or mid == Cin, (mid, Cin)
    assert not residual or (stride == 1 and Cin == Cout)
    OH = -(-H // stride)
    OW = -(-W // stride)
    ph = max((OH - 1) * stride + R - H, 0)
    pw = max((OW - 1) * stride + S - W, 0)
    pads = ((ph // 2, ph - ph // 2), (pw // 2, pw - pw // 2))
    tm = min(block_m, mid)
    if not expanded or mid % tm:
        tm = mid  # single slab: t == 1 slabs ride the unsliced input
    nm = mid // tm
    grid = (B, nm)
    xq = phases.split(x, stride)
    operands = [xq]
    in_specs = [
        # index map ignores m -> the input (and residual identity) stays
        # resident across the whole slab row
        pl.BlockSpec((1,) + xq.shape[1:], lambda b, m: (b, 0, 0, 0, 0)),
    ]
    row = pl.BlockSpec((1, tm), lambda b, m: (0, m))
    if expanded:
        operands += [w1, _vec_or(weights.get("s1"), mid, 1.0),
                     _vec_or(weights.get("b1"), mid, 0.0)]
        in_specs += [pl.BlockSpec((1, 1, Cin, tm), lambda b, m: (0, 0, 0, m)),
                     row, row]
    operands += [wdw, _vec_or(weights.get("sdw"), mid, 1.0),
                 _vec_or(weights.get("bdw"), mid, 0.0),
                 w2, _vec_or(weights.get("s2"), Cout, 1.0),
                 _vec_or(weights.get("b2"), Cout, 0.0)]
    full = pl.BlockSpec((1, Cout), lambda b, m: (0, 0))
    in_specs += [pl.BlockSpec((R, S, 1, tm), lambda b, m: (0, 0, 0, m)),
                 row, row,
                 pl.BlockSpec((1, 1, tm, Cout), lambda b, m: (0, 0, m, 0)),
                 full, full]
    return pl.pallas_call(
        functools.partial(_ir_kernel, H=H, W=W, OH=OH, OW=OW, R=R, S=S,
                          stride=stride, pads=pads, act=act, out_act=out_act,
                          residual=residual, expanded=expanded, nm=nm,
                          compute_dtype=x.dtype),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, OH, OW, Cout), lambda b, m: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, OH, OW, Cout), x.dtype),
        scratch_shapes=[pltpu.VMEM((OH * OW, Cout), jnp.float32)],
        interpret=interpret,
    )(*operands)


# ----------------------------------------------------------------------
# residual conv: the ResNet block tail with the shortcut add fused


def _rc_kernel(x_ref, w_ref, s_ref, b_ref, res_ref, o_ref, *, H, W, R, S,
               act):
    """ilpm-style tap loop (image resident, K on lanes) plus a residual
    operand slab; the shortcut add and the block's outer activation fold
    into the single output write."""
    C = x_ref.shape[-1]
    TK = w_ref.shape[-1]
    acc = jnp.zeros((H * W, TK), jnp.float32)
    for r in range(R):
        for s in range(S):
            xs = x_ref[0, r:r + H, s:s + W, :].reshape(H * W, C)
            acc += jnp.dot(xs, w_ref[r, s],
                           preferred_element_type=jnp.float32)
    # the conv's own folded-BN write (cast where the per-layer kernel
    # casts), then the shortcut add + outer act in the compute dtype —
    # the exact op order of the unfused `act(conv(x) + identity)`
    y = (acc * s_ref[0] + b_ref[0]).astype(o_ref.dtype)
    y = apply_act(y + res_ref[0].reshape(H * W, TK), act)
    o_ref[0] = y.reshape(H, W, TK)


@functools.partial(jax.jit, static_argnames=("block_k", "act", "interpret"))
def fused_residual_conv(x_padded, weights, *, res, block_k: int = 128,
                        act: str | None = "relu", interpret: bool = False):
    """x_padded: (B, H+R-1, W+S-1, C) pre-padded (stride 1 only — every
    ResNet block's *second* conv is stride 1); weights: ``w`` (R, S, C, K)
    + ``scale``/``bias`` (K,); ``res``: the (B, H, W, K) shortcut branch
    (identity or projection output) -> (B, H, W, K).

    Equivalent to ``act(conv(x)*scale + bias + res)`` with the add and
    activation fused into the conv's output write: the unfused chain pays
    an extra read-modify-write pass over the conv output.
    """
    B, Hp, Wp, C = x_padded.shape
    R, S, _, K = weights["w"].shape
    H, W = Hp - R + 1, Wp - S + 1
    assert res.shape == (B, H, W, K), (res.shape, (B, H, W, K))
    tk = min(block_k, K)
    grid = (B, pl.cdiv(K, tk))
    operands = [x_padded, weights["w"],
                _vec_or(weights.get("scale"), K, 1.0),
                _vec_or(weights.get("bias"), K, 0.0), res]
    row = pl.BlockSpec((1, tk), lambda b, k: (0, k))
    in_specs = [
        pl.BlockSpec((1, Hp, Wp, C), lambda b, k: (b, 0, 0, 0)),
        pl.BlockSpec((R, S, C, tk), lambda b, k: (0, 0, 0, k)),
        row, row,
        pl.BlockSpec((1, H, W, tk), lambda b, k: (b, 0, 0, k)),
    ]
    return pl.pallas_call(
        functools.partial(_rc_kernel, H=H, W=W, R=R, S=S, act=act),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, H, W, tk), lambda b, k: (b, 0, 0, k)),
        out_shape=jax.ShapeDtypeStruct((B, H, W, K), x_padded.dtype),
        interpret=interpret,
    )(*operands)
