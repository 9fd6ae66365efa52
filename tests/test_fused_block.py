"""Fused-block megakernels: adversarial parity vs the composed per-layer
chain, the cost model's saved-round-trip charging rule, block-carrying
plan round-trips, and the full-network fused-vs-per-layer acceptance bar.

Two parity tiers, on purpose:

  * vs the fp32 *reference* chain (``ref.fused_inverted_residual``) the
    fused kernel holds the documented ``tolerance(dtype)`` across the
    stride x expansion x dtype x residual matrix — the same contract every
    per-conv kernel signs in test_precision.py;
  * vs the composed per-layer *Pallas* chain at fp32 the fused kernel is
    BITWISE equal (it mirrors those kernels' accumulation stage for
    stage), which is what makes the fused-plan vs per-layer-plan
    full-network logits comparison exact rather than approximate.
"""
import copy
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.core import (ConvSpec, FusedBlockSpec, InferenceEngine,
                        TuningPlan, build_plan, select_block)
from repro.core.autotune import block_baseline_time, block_constituents
from repro.core.dtypes import KERNEL_DTYPES, tolerance
from repro.kernels import ops, ref

KEY = jax.random.key(7)


def _ir_weights(cin, mid, cout, dtype, r=3):
    """A full inverted-residual weight set; the expansion stage (w1/s1/b1)
    is included only when mid != cin (t > 1)."""
    dt = jnp.dtype(dtype)
    k = jax.random.fold_in(KEY, cin * mid * cout)
    ks = jax.random.split(k, 9)
    w = {"wdw": jax.random.normal(ks[0], (r, r, 1, mid), dt),
         "sdw": jax.random.normal(ks[1], (mid,)) * 0.5 + 1.0,
         "bdw": jax.random.normal(ks[2], (mid,)) * 0.1,
         "w2": jax.random.normal(ks[3], (1, 1, mid, cout), dt) * 0.2,
         "s2": jax.random.normal(ks[4], (cout,)) * 0.5 + 1.0,
         "b2": jax.random.normal(ks[5], (cout,)) * 0.1}
    if mid != cin:
        w.update({"w1": jax.random.normal(ks[6], (1, 1, cin, mid), dt) * 0.3,
                  "s1": jax.random.normal(ks[7], (mid,)) * 0.5 + 1.0,
                  "b1": jax.random.normal(ks[8], (mid,)) * 0.1})
    return w


# residual demands stride == 1 and cin == cout; everything else sweeps
_IR_CASES = [(stride, t, residual)
             for stride in (1, 2) for t in (1, 6)
             for residual in (False, True)
             if not (residual and stride == 2)]


@pytest.mark.parametrize("dtype", KERNEL_DTYPES)
@pytest.mark.parametrize("stride,t,residual", _IR_CASES,
                         ids=lambda v: str(v))
def test_fused_inverted_residual_parity_vs_reference(stride, t, residual,
                                                     dtype):
    """Fused megakernel on dtype inputs vs the fp32 composed reference of
    the same values: within the documented tolerance(dtype)."""
    cin = 8
    cout = cin if residual else 16
    dt = jnp.dtype(dtype)
    x = jax.random.normal(KEY, (1, 8, 8, cin), dt)
    w = _ir_weights(cin, cin * t, cout, dtype)
    gt = ref.fused_inverted_residual(
        x.astype(jnp.float32),
        {k: v.astype(jnp.float32) for k, v in w.items()},
        stride=stride, residual=residual)
    y = ops.fused_inverted_residual(x, w, impl="pallas", stride=stride,
                                    residual=residual)
    assert y.dtype == dt  # cast-on-write: output carries the input dtype
    assert y.shape == gt.shape
    rel = float(jnp.abs(y.astype(jnp.float32) - gt).max()
                / (jnp.abs(gt).max() + 1e-12))
    assert rel < tolerance(dtype), (stride, t, residual, dtype, rel)


@pytest.mark.parametrize("stride,t,residual", _IR_CASES,
                         ids=lambda v: str(v))
def test_fused_inverted_residual_bitwise_vs_per_layer_pallas(stride, t,
                                                             residual):
    """At fp32 the fused kernel is bitwise equal to the composed per-layer
    Pallas chain (expand -> pad -> depthwise -> project [-> +x]) — it
    mirrors those kernels' accumulation and cast points exactly. This is
    the kernel-level fact underneath the full-network logits equality."""
    cin = 8
    cout = cin if residual else 16
    x = jax.random.normal(KEY, (1, 8, 8, cin))
    w = _ir_weights(cin, cin * t, cout, "float32")
    e = x
    if t > 1:
        e = ops.dispatch("pointwise", x, w["w1"], impl="pallas",
                         scale=w["s1"], bias=w["b1"], act="relu6")
    ep = ref.pad_same(e, 3, 3, stride)
    d = ops.dispatch("depthwise", ep, w["wdw"], impl="pallas",
                     stride=stride, scale=w["sdw"], bias=w["bdw"],
                     act="relu6")
    y = ops.dispatch("pointwise", d, w["w2"], impl="pallas",
                     scale=w["s2"], bias=w["b2"])
    if residual:
        y = y + x
    yf = ops.fused_inverted_residual(x, w, impl="pallas", stride=stride,
                                     residual=residual)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(y))


# MobileNetV2's stride-2 blocks cut to size: (H, Cin, mid, Cout, block_m).
# The kernel expands the input's stride phases and reads every depthwise
# tap as a contiguous window — even and odd sizes, one slab and several,
# expanded widths (144, 96) Mosaic refused to read with a stride
_IR_STRIDE2 = [(16, 24, 144, 32, 144), (15, 24, 144, 32, 48),
               (9, 16, 96, 24, 96)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h,cin,mid,cout,block_m", _IR_STRIDE2, ids=str)
def test_fused_inverted_residual_stride2_phases_vs_reference(
        h, cin, mid, cout, block_m, dtype):
    dt = jnp.dtype(dtype)
    x = jax.random.normal(KEY, (1, h, h, cin), dt)
    w = _ir_weights(cin, mid, cout, dtype)
    gt = ref.fused_inverted_residual(
        x.astype(jnp.float32),
        {k: v.astype(jnp.float32) for k, v in w.items()}, stride=2)
    y = ops.fused_inverted_residual(x, w, impl="pallas", stride=2,
                                    block_m=block_m)
    assert y.shape == gt.shape == (1, -(-h // 2), -(-h // 2), cout)
    rel = float(jnp.abs(y.astype(jnp.float32) - gt).max()
                / jnp.abs(gt).max())
    assert rel < tolerance(dtype), (h, mid, block_m, dtype, rel)


def test_fused_inverted_residual_multi_slab_matches_single_slab():
    """Slicing the expanded width into slabs (the tuned block_m) only
    reorders the projection's fp32 accumulation; a non-dividing block_m
    falls back to the single-slab variant rather than double-counting a
    ragged slab."""
    x = jax.random.normal(KEY, (1, 8, 8, 8))
    w = _ir_weights(8, 48, 16, "float32")
    y1 = ops.fused_inverted_residual(x, w, impl="pallas", block_m=48)
    y2 = ops.fused_inverted_residual(x, w, impl="pallas", block_m=24)
    y3 = ops.fused_inverted_residual(x, w, impl="pallas", block_m=20)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(y3), np.asarray(y1))


@pytest.mark.parametrize("rs,block_k", [(3, 128), (1, 128), (3, 16)],
                         ids=("3x3", "1x1", "ragged-k"))
def test_fused_residual_conv_bitwise_vs_per_layer_pallas(rs, block_k):
    """conv + shortcut add + outer ReLU in one write == the per-layer
    ilpm conv followed by the separate add pass, bitwise at fp32 —
    including a block_k that does not divide K."""
    C, K = 16, 24
    x = jax.random.normal(KEY, (1, 8, 8, C))
    ks = jax.random.split(jax.random.fold_in(KEY, rs), 4)
    w = {"w": jax.random.normal(ks[0], (rs, rs, C, K)) * 0.2,
         "scale": jax.random.normal(ks[1], (K,)) * 0.5 + 1.0,
         "bias": jax.random.normal(ks[2], (K,)) * 0.1}
    res = jax.random.normal(ks[3], (1, 8, 8, K))
    xp = ref.pad_same(x, rs, rs)
    y = ops.dispatch("ilpm", xp, w["w"], impl="pallas",
                     scale=w["scale"], bias=w["bias"])
    y = ref.apply_act(y + res, "relu")
    yf = ops.fused_residual_conv(xp, w, impl="pallas", res=res,
                                 block_k=block_k)
    np.testing.assert_array_equal(np.asarray(yf), np.asarray(y))


@pytest.mark.parametrize("dtype", ["bfloat16", "float16"])
def test_fused_residual_conv_reduced_precision_parity(dtype):
    dt = jnp.dtype(dtype)
    x = jax.random.normal(KEY, (1, 8, 8, 16), dt)
    ks = jax.random.split(KEY, 2)
    w = {"w": jax.random.normal(ks[0], (3, 3, 16, 16), dt) * 0.2}
    res = jax.random.normal(ks[1], (1, 8, 8, 16), dt)
    xp = ref.pad_same(x, 3, 3)
    gt = ref.fused_residual_conv(xp.astype(jnp.float32),
                                 {"w": w["w"].astype(jnp.float32)},
                                 res=res.astype(jnp.float32))
    y = ops.fused_residual_conv(xp, w, impl="pallas", res=res)
    assert y.dtype == dt
    rel = float(jnp.abs(y.astype(jnp.float32) - gt).max()
                / (jnp.abs(gt).max() + 1e-12))
    assert rel < tolerance(dtype), rel


# ----------------------------------------------------------------------
# the cost model's charging rule


def _ir_bspec(dtype="float32"):
    return FusedBlockSpec("inverted_residual", h=16, w=16, cin=24, mid=144,
                          cout=32, stride=2, dtype=dtype)


def _block_specs_under_test():
    return [_ir_bspec(),
            FusedBlockSpec("inverted_residual", h=8, w=8, cin=32, mid=192,
                           cout=32, residual=True),
            FusedBlockSpec("inverted_residual", h=16, w=16, cin=32, mid=32,
                           cout=32, residual=True),  # t == 1
            FusedBlockSpec("residual_conv", h=8, w=8, cin=64, mid=64,
                           cout=64, residual=True),
            FusedBlockSpec("residual_conv", h=8, w=8, cin=64, mid=64,
                           cout=256, r=1, s=1, residual=True)]


@pytest.mark.parametrize("bspec", _block_specs_under_test(),
                         ids=lambda b: f"{b.kind}-{b.mid}-{b.cout}")
def test_fused_bytes_below_per_layer_sum_by_exactly_saved_bytes(bspec):
    """The charging rule, to the byte: the fused candidate's HBM estimate
    is the per-layer constituent sum minus the round-trips that now stay
    in VMEM (plus, for residual_conv only, one read of the shortcut
    operand — a different tensor, unlike the inverted residual's identity,
    which is the already-resident input)."""
    ch = select_block(bspec)
    assert ch is not None  # the tuner fuses every one of these sites
    per_layer = sum(c.est_bytes for c in block_constituents(bspec))
    shortcut_read = (bspec.element_size * bspec.batch * bspec.out_h
                     * bspec.out_w * bspec.cout
                     if bspec.kind == "residual_conv" else 0)
    assert ch.est_bytes == per_layer - bspec.saved_bytes + shortcut_read
    assert ch.est_bytes < per_layer  # strictly below the constituent sum
    assert ch.est_time < block_baseline_time(bspec)


def test_saved_bytes_scale_with_dtype():
    """Halving the element width halves the saved round-trip — dtype is
    part of the block tuning key for the same reason it is for ConvSpec."""
    b32 = _ir_bspec()
    b16 = dataclasses.replace(b32, dtype="bfloat16")
    assert b32.saved_bytes > 0
    assert b16.saved_bytes * 2 == b32.saved_bytes
    ch32, ch16 = select_block(b32), select_block(b16)
    assert ch16 is not None and ch16.est_bytes < ch32.est_bytes


def test_select_block_prefers_single_slab_and_dividing_block_m():
    """Every slab width moves the same bytes, so the single-slab variant
    (bitwise-identical reduction order to the per-layer chain) wins ties;
    any tuned block_m divides mid exactly."""
    ch = select_block(_ir_bspec())
    assert ch.algorithm == "fused_inverted_residual"
    bm = dict(ch.params)["block_m"]
    assert _ir_bspec().mid % bm == 0


def test_build_plan_records_block_winners_and_keeps_conv_entries():
    """Block fusion is additive: the plan still carries a per-conv entry
    for every constituent site, so it deploys on engines without block
    support; the block winner rides in its own `<name>.block` section."""
    bspec = _ir_bspec()
    conv_specs = [(f"blk.{n}", cs) for n, cs in bspec.conv_specs()]
    plan = build_plan(conv_specs, block_specs=[("blk.block", bspec)])
    assert set(plan.choices) == {n for n, _ in conv_specs}
    assert set(plan.block_choices) == {"blk.block"}
    assert plan.block_choices["blk.block"].algorithm \
        == "fused_inverted_residual"


# ----------------------------------------------------------------------
# plans carry blocks: round-trip, deploy, cross-dtype rejection


def test_mixed_plan_json_round_trip(tmp_path):
    conv_specs = [("a", ConvSpec(h=8, w=8, c=16, k=16)),
                  ("b", ConvSpec(h=8, w=8, c=16, k=32, r=1, s=1))]
    blocks = [("ir.block", _ir_bspec()),
              ("rc.block", FusedBlockSpec("residual_conv", h=8, w=8,
                                          cin=64, mid=64, cout=64,
                                          residual=True))]
    plan = build_plan(conv_specs, block_specs=blocks)
    assert len(plan.block_choices) == 2
    back = TuningPlan.from_json(plan.to_json())
    assert back.choices == plan.choices
    assert back.block_specs == plan.block_specs
    assert back.block_choices == plan.block_choices
    path = tmp_path / "plan.json"
    plan.save(path)
    loaded = TuningPlan.load(path)
    assert loaded.block_specs == plan.block_specs
    assert loaded.block_choices == plan.block_choices


def test_block_plan_survives_save_load_deploy(tmp_path):
    """Tune-once / deploy-many holds for block-carrying plans: the loaded
    plan drives the same fused dispatch and the same logits."""
    cfg = tiny_variant(get("mobilenet_v2"))
    eng = InferenceEngine(cfg)
    assert eng.plan.block_choices  # acceptance: >= 1 fused block
    path = tmp_path / "plan.json"
    eng.save_plan(path)
    img = jax.random.normal(KEY, (32, 32, 3))
    eng2 = InferenceEngine(cfg, params=eng.params, plan=str(path))
    assert eng2.plan.block_choices == eng.plan.block_choices
    np.testing.assert_array_equal(np.asarray(eng2.run(img)),
                                  np.asarray(eng.run(img)))


def test_engine_rejects_cross_dtype_block_plan():
    """Per-conv entries matching is not enough: a block entry tuned at a
    different dtype must fail deploy validation (its saved-bytes
    accounting — and its kernel's cast points — are dtype-specific)."""
    cfg = tiny_variant(get("mobilenet_v2"))
    eng = InferenceEngine(cfg)
    bad = copy.deepcopy(eng.plan)
    bad.block_specs = {n: dataclasses.replace(s, dtype="bfloat16")
                       for n, s in bad.block_specs.items()}
    with pytest.raises(ValueError, match="mismatched block specs"):
        InferenceEngine(cfg, params=eng.params, plan=bad)


# ----------------------------------------------------------------------
# the acceptance bar: whole-network logits, fused plan vs per-layer plan


def _strip_blocks(plan):
    p = copy.deepcopy(plan)
    p.block_choices.clear()
    p.block_specs.clear()
    return p


@pytest.mark.parametrize("network", ["mobilenet_v2", "resnet18"])
def test_full_network_fused_vs_per_layer_logits_bitwise(network):
    """At fp32 the fused-plan forward and the per-layer-plan forward
    produce bitwise-identical logits: fusion changes where intermediates
    live (VMEM vs HBM), never a single ULP of the math."""
    cfg = tiny_variant(get(network))
    eng = InferenceEngine(cfg)
    assert eng.plan.block_choices, network
    img = jax.random.normal(KEY, (32, 32, 3))
    fused = np.asarray(eng.run(img))
    per_layer_eng = InferenceEngine(cfg, params=eng.params,
                                    plan=_strip_blocks(eng.plan))
    np.testing.assert_array_equal(np.asarray(per_layer_eng.run(img)), fused)
    assert not np.isnan(fused).any()
