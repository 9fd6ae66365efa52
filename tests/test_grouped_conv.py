"""Grouped-conv family tests: depthwise/pointwise Pallas kernels (interpret
mode) against the lax.conv_general_dilated ground truth across strides and
channel counts, group-aware ConvSpec accounting, tuner coverage, and the
MobileNet-style forward under a tuned per-layer plan."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import spy_algorithms as _spy_algorithms
from repro.configs import get, tiny_variant
from repro.core import ConvSpec, InferenceEngine, conv2d
from repro.core.autotune import cost_model_select, measured_select
from repro.core.dtypes import tolerance
from repro.kernels import ops, ref

KEY = jax.random.key(0)

# (H, W, C) x stride — odd sizes and ragged channel counts included
DW_CASES = [
    (16, 16, 8, 1),
    (16, 16, 8, 2),
    (14, 14, 96, 1),    # MobileNetV2 s4 shape
    (14, 14, 144, 2),   # strided downsample, C > one lane block
    (13, 11, 40, 2),    # odd dims: SAME padding asymmetry under stride
    (7, 7, 160, 1),
]


def _dw_inputs(h, w, c, dtype=jnp.float32):
    x = jax.random.normal(KEY, (1, h, w, c), dtype)
    wgt = jax.random.normal(jax.random.fold_in(KEY, 7), (3, 3, 1, c), dtype)
    return x, wgt


@pytest.mark.parametrize("case", DW_CASES, ids=str)
def test_depthwise_kernel_vs_ground_truth(case):
    h, w, c, stride = case
    x, wgt = _dw_inputs(h, w, c)
    gt = ref.conv2d_reference(x, wgt, stride=stride, groups=c)
    xp = ref.pad_same(x, 3, 3, stride=stride)
    y = ops.depthwise(xp, wgt, impl="pallas", stride=stride)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-4,
                               atol=1e-4 * float(jnp.abs(gt).max()))


# MobileNetV2's stride-2 depthwise sites cut to size: the kernel reads its
# taps from stride phases (``phases.split``) — even and odd sizes, slabs
# of 128 lanes with a ragged last one, widths Mosaic refused to read with
# a stride (144, 200: wider than 128 lanes)
DW_STRIDE2_CASES = [(16, 16, 144), (15, 13, 96), (9, 9, 200)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", DW_STRIDE2_CASES, ids=str)
def test_depthwise_stride2_phases_vs_reference(case, dtype):
    h, w, c = case
    x, wgt = _dw_inputs(h, w, c, jnp.dtype(dtype))
    gt = ref.conv2d_reference(x.astype(jnp.float32),
                              wgt.astype(jnp.float32), stride=2, groups=c)
    xp = ref.pad_same(x, 3, 3, stride=2)
    y = ops.depthwise(xp, wgt, impl="pallas", stride=2, block_c=128)
    assert y.shape == gt.shape and y.dtype == jnp.dtype(dtype)
    rel = float(jnp.abs(y.astype(jnp.float32) - gt).max()
                / jnp.abs(gt).max())
    assert rel < tolerance(dtype), (case, dtype, rel)


@pytest.mark.parametrize("block_c", [8, 32, 128, 512])
def test_depthwise_block_sweep(block_c):
    x, wgt = _dw_inputs(10, 12, 48)  # 48 % 32 != 0: ragged last block
    gt = ref.conv2d_reference(x, wgt, groups=48)
    xp = ref.pad_same(x, 3, 3)
    y = ops.depthwise(xp, wgt, impl="pallas", block_c=block_c)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-4,
                               atol=1e-4)


def test_depthwise_pallas_vs_structural_ref():
    x, wgt = _dw_inputs(12, 12, 32)
    xp = ref.pad_same(x, 3, 3, stride=2)
    y_pl = ops.depthwise(xp, wgt, impl="pallas", stride=2)
    y_ref = ops.depthwise(xp, wgt, impl="jnp", stride=2)
    np.testing.assert_allclose(np.asarray(y_pl), np.asarray(y_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("ck", [(8, 16), (48, 24), (96, 576), (130, 40)])
def test_pointwise_kernel_vs_ground_truth(ck):
    c, k = ck
    x = jax.random.normal(KEY, (1, 9, 11, c))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 1, c, k))
    gt = ref.conv2d_reference(x, wgt)
    for block_k in (16, 128, 512):
        y = ops.pointwise(x, wgt, impl="pallas", block_k=block_k)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(gt), rtol=1e-4,
            atol=1e-4 * float(jnp.abs(gt).max()), err_msg=str(block_k))


@pytest.mark.parametrize("stride", [1, 2])
def test_conv2d_grouped_routing(stride):
    """conv2d detects groups from the filter shape and matches lax for
    both the auto (tuned) path and the xla escape hatch."""
    x = jax.random.normal(KEY, (1, 16, 16, 24))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 5), (3, 3, 1, 24))
    gt = ref.conv2d_reference(x, wgt, stride=stride, groups=24)
    for algorithm in ("auto", "xla"):
        y = conv2d(x, wgt, stride=stride, algorithm=algorithm)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-4,
                                   atol=1e-4, err_msg=algorithm)


def test_conv2d_grouped_non_depthwise_falls_back():
    """groups > 1 but != C (grouped, not depthwise): XLA reference path."""
    x = jax.random.normal(KEY, (1, 8, 8, 16))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 6), (3, 3, 4, 32))
    gt = ref.conv2d_reference(x, wgt, groups=4)
    y = conv2d(x, wgt, algorithm="auto")
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-5,
                               atol=1e-5)


def test_convspec_group_accounting():
    """Depthwise flops/bytes divide the dense C*K product by groups."""
    dense = ConvSpec(h=14, w=14, c=96, k=96)
    dw = ConvSpec(h=14, w=14, c=96, k=96, groups=96)
    assert dw.flops == dense.flops // 96
    el = 4
    assert dw.bytes_min == dense.bytes_min - el * 3 * 3 * 96 * 95  # filters


def test_convspec_from_tensors_group_aware():
    """Depthwise weights (r,s,1,c) must produce groups=c, not a wrong c."""
    x = jax.random.normal(KEY, (1, 8, 8, 24))
    wgt = jax.random.normal(KEY, (3, 3, 1, 24))
    spec = ConvSpec.from_tensors(x, wgt, 2)
    assert (spec.c, spec.k, spec.groups, spec.stride) == (24, 24, 24, 2)
    assert spec.depthwise
    # dense filters unchanged
    wd = jax.random.normal(KEY, (3, 3, 24, 32))
    spec = ConvSpec.from_tensors(x, wd, 1)
    assert (spec.c, spec.k, spec.groups) == (24, 32, 1)


def test_tuner_on_grouped_specs():
    """Cost model and measured mode both pick the grouped kernels for
    grouped specs, including strided depthwise (in-kernel downsample)."""
    for stride in (1, 2):
        spec = ConvSpec(h=16, w=16, c=96, k=96, groups=96, stride=stride)
        assert cost_model_select(spec).algorithm == "depthwise"
        assert measured_select(spec, repeats=1).algorithm == "depthwise"
    pw = ConvSpec(h=16, w=16, c=96, k=192, r=1, s=1)
    assert cost_model_select(pw).algorithm == "pointwise"
    assert measured_select(pw, repeats=1).algorithm == "pointwise"
    # strided pointwise subsamples in-kernel (ResNet projection shortcuts)
    assert cost_model_select(
        ConvSpec(h=16, w=16, c=96, k=192, r=1, s=1, stride=2)
    ).algorithm == "pointwise"
    # grouped-non-depthwise: no kernel family -> xla
    assert cost_model_select(
        ConvSpec(h=16, w=16, c=96, k=96, groups=4)).algorithm == "xla"


def test_mobilenet_tuned_plan_end_to_end(monkeypatch):
    """The acceptance path: a MobileNet-style forward runs through a tuned
    plan (cost-model mode) where fused inverted-residual blocks dispatch
    ONE megakernel each (per-layer would have dispatched two or three),
    every unfused depthwise/pointwise site goes through ops.dispatch, and
    the result matches the all-XLA reference."""
    cfg = tiny_variant(get("mobilenet_v2"))
    calls = _spy_algorithms(monkeypatch)  # records (algorithm, params)
    eng = InferenceEngine(cfg)  # algorithm="auto": builds a plan
    plan = eng.plan
    dw_sites = [n for n, s in plan.specs.items() if s.groups > 1]
    pw_sites = [n for n, s in plan.specs.items()
                if s.groups == 1 and s.r == 1]
    assert dw_sites and pw_sites
    # per-conv entries are always planned, even for blocks that fuse —
    # the plan stays deployable on engines without block support
    assert all(plan.choices[n].algorithm == "depthwise" for n in dw_sites)
    assert all(plan.choices[n].algorithm == "pointwise" for n in pw_sites)
    # the strided dense stem runs a strided Pallas kernel, not xla
    assert plan.choices["stem"].algorithm in ("ilpm", "direct")
    # strided depthwise sites are planned, not punted to xla
    assert any(plan.specs[n].stride == 2 for n in dw_sites)
    # the tuner fuses at least one inverted-residual block (acceptance
    # criterion: the expanded tensor never round-trips through HBM there)
    assert plan.block_choices
    assert all(c.algorithm == "fused_inverted_residual"
               for c in plan.block_choices.values())

    img = jax.random.normal(KEY, (32, 32, 3))
    logits = eng.run(img)
    assert logits.shape == (cfg.vocab_size,)
    assert not bool(jnp.isnan(logits).any())
    dispatched = [name for name, _ in calls]
    # each fused block produces exactly ONE dispatch...
    assert (dispatched.count("fused_inverted_residual")
            == len(plan.block_choices))
    # ...and its constituent convs are not dispatched separately; unfused
    # dw/pw sites (and the head projection) still run their tuned kernels
    fused_convs = {f"{b[:-len('.block')]}.{sfx}"
                   for b in plan.block_choices
                   for sfx, _ in plan.block_specs[b].conv_specs()}
    assert dispatched.count("depthwise") == len(
        [n for n in dw_sites if n not in fused_convs])
    assert dispatched.count("pointwise") == len(
        [n for n in pw_sites if n not in fused_convs])

    ref_eng = InferenceEngine(cfg, params=eng.params, algorithm="xla")
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_eng.run(img)),
                               rtol=1e-3, atol=1e-3)
