"""Strided-kernel + fused-epilogue coverage: strided ilpm/direct/pointwise
sweeps against the lax ground truth, epilogue-fusion parity (conv+BN+act in
one kernel pass vs the unfused reference), depthwise channel multipliers,
whole-backbone plan coverage (zero xla choices for dense conv sites), and
the once-per-engine Winograd filter-transform cache."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.core import ConvSpec, InferenceEngine, TuningPlan, conv2d
from repro.core.autotune import Choice, cost_model_select, tunable
from repro.core.dtypes import tolerance
from repro.kernels import ops, phases, ref

KEY = jax.random.key(0)


def _mk(b, h, w, c, k, r=3, s=3):
    x = jax.random.normal(KEY, (b, h, w, c))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 7), (r, s, c, k))
    return x, wgt


# ---------------------------------------------------------------------
# thin strided stems: folded into channels (space-to-depth)

# (H, R): the ResNet 7x7/2 and MobileNetV2 3x3/2 RGB stems at small sizes,
# even and odd
STEM_CASES = [(32, 7), (33, 7), (32, 3), (17, 3)]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("h,r", STEM_CASES, ids=str)
def test_rgb_stem_folds_into_stride1_conv(h, r, dtype):
    """An RGB stride-2 stem runs as a ceil(R/2)-tap stride-1 conv over
    12 channels: the fold is exact, and the kernel with its epilogue holds
    the dtype's tolerance against the fp32 reference."""
    x, wgt = _mk(1, h, h, 3, 16, r=r, s=r)
    xp = ref.pad_same(x, r, r, stride=2)
    assert phases.foldable(3, 2) and not phases.foldable(64, 2)
    xq, wq = phases.fold(xp, wgt, 2)
    assert wq.shape == (-(-r // 2), -(-r // 2), 12, 16)
    np.testing.assert_allclose(
        np.asarray(ref.ilpm_conv(xq, wq)),
        np.asarray(ref.ilpm_conv(xp, wgt, stride=2)), rtol=1e-5, atol=1e-4)
    scale = jax.random.uniform(jax.random.fold_in(KEY, 3), (16,)) + 0.5
    bias = jax.random.normal(jax.random.fold_in(KEY, 4), (16,))
    gt = ref.apply_epilogue(ref.conv2d_reference(x, wgt, stride=2),
                            scale=scale, bias=bias, act="relu")
    dt = jnp.dtype(dtype)
    y = ops.ilpm(xp.astype(dt), wgt.astype(dt), impl="pallas", stride=2,
                 block_k=64, scale=scale, bias=bias, act="relu")
    assert y.shape == gt.shape and y.dtype == dt
    rel = float(jnp.abs(y.astype(jnp.float32) - gt).max()
                / jnp.abs(gt).max())
    assert rel < tolerance(dtype), (h, r, dtype, rel)


# ---------------------------------------------------------------------
# strided dense kernels

# (H, W, C, K, R) — odd H/W, the stem's 7x7, ragged channels
STRIDED_CASES = [
    (16, 16, 8, 16, 3),
    (13, 11, 8, 24, 3),     # odd dims: SAME padding asymmetry under stride
    (32, 32, 3, 64, 7),     # the ResNet stem shape class
    (15, 9, 5, 13, 7),      # odd everything
    (8, 8, 16, 130, 3),     # K > one lane block, ragged
]


@pytest.mark.parametrize("case", STRIDED_CASES, ids=str)
@pytest.mark.parametrize("algo", ["ilpm", "direct"])
def test_strided_dense_kernel_vs_ground_truth(case, algo):
    h, w, c, k, r = case
    x, wgt = _mk(1, h, w, c, k, r=r, s=r)
    gt = ref.conv2d_reference(x, wgt, stride=2)
    xp = ref.pad_same(x, r, r, stride=2)
    for impl in ("pallas", "jnp"):
        y = ops.ALGORITHMS[algo](xp, wgt, impl=impl, stride=2)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(gt), rtol=2e-4,
            atol=2e-4 * float(jnp.abs(gt).max()), err_msg=f"{algo}/{impl}")


@pytest.mark.parametrize("block", [2, 4, 8])
def test_strided_direct_block_sweep(block):
    x, wgt = _mk(1, 13, 11, 8, 16)
    gt = ref.conv2d_reference(x, wgt, stride=2)
    xp = ref.pad_same(x, 3, 3, stride=2)
    y = ops.direct(xp, wgt, impl="pallas", stride=2, block_h=block)
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=2e-4,
                               atol=1e-3)


@pytest.mark.parametrize("hw", [(16, 16), (13, 11), (7, 7)])
def test_strided_pointwise_vs_ground_truth(hw):
    h, w = hw
    x = jax.random.normal(KEY, (1, h, w, 24))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 1, 24, 40))
    gt = ref.conv2d_reference(x, wgt, stride=2)
    for impl in ("pallas", "jnp"):
        y = ops.pointwise(x, wgt, impl=impl, stride=2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=2e-4,
                                   atol=1e-3, err_msg=impl)


def test_strided_conv2d_routes_to_kernels():
    """conv2d at stride 2 dispatches the strided kernels (and redirects
    the stride-1-only algorithms to ilpm) — full-precision vs lax."""
    x, wgt = _mk(1, 14, 14, 8, 16)
    gt = ref.conv2d_reference(x, wgt, stride=2)
    for algo in ("auto", "ilpm", "direct", "winograd", "im2col"):
        y = conv2d(x, wgt, stride=2, algorithm=algo)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=2e-4,
                                   atol=1e-3, err_msg=algo)


def test_tunable_covers_strided_classes():
    assert tunable(ConvSpec(h=16, w=16, c=8, k=16, stride=2))
    assert tunable(ConvSpec(h=32, w=32, c=3, k=64, r=7, s=7, stride=2))
    assert tunable(ConvSpec(h=16, w=16, c=8, k=16, r=1, s=1, stride=2))
    assert not tunable(ConvSpec(h=16, w=16, c=8, k=16, stride=4))
    # strided candidates enumerate only the in-kernel-downsampling families
    ch = cost_model_select(ConvSpec(h=56, w=56, c=64, k=64, stride=2))
    assert ch.algorithm in ("ilpm", "direct")


# ---------------------------------------------------------------------
# fused epilogue parity

EPILOGUE_ALGOS = ["ilpm", "direct", "im2col", "libdnn", "winograd"]


@pytest.mark.parametrize("algo", EPILOGUE_ALGOS)
@pytest.mark.parametrize("act", [None, "relu", "relu6"])
def test_dense_epilogue_fusion_parity(algo, act):
    """conv+scale+bias+act fused in-kernel == unfused reference (fp32)."""
    x, wgt = _mk(1, 12, 12, 8, 16)
    sc = jax.random.normal(jax.random.fold_in(KEY, 11), (16,))
    bi = jax.random.normal(jax.random.fold_in(KEY, 12), (16,))
    xp = ref.pad_same(x, 3, 3)
    want = ref.apply_epilogue(ref.conv2d_reference(x, wgt), scale=sc,
                              bias=bi, act=act)
    for impl in ("pallas", "jnp"):
        y = ops.ALGORITHMS[algo](xp, wgt, impl=impl, scale=sc, bias=bi,
                                 act=act)
        np.testing.assert_allclose(
            np.asarray(y), np.asarray(want), rtol=2e-4,
            atol=2e-4 * float(jnp.abs(want).max() + 1),
            err_msg=f"{algo}/{impl}")


def test_grouped_epilogue_fusion_parity():
    x = jax.random.normal(KEY, (1, 10, 10, 12))
    dw = jax.random.normal(jax.random.fold_in(KEY, 5), (3, 3, 1, 12))
    pw = jax.random.normal(jax.random.fold_in(KEY, 6), (1, 1, 12, 20))
    for w, k, algo, gt in [
            (dw, 12, "depthwise", ref.conv2d_reference(x, dw, groups=12)),
            (pw, 20, "pointwise", ref.conv2d_reference(x, pw))]:
        sc = jax.random.normal(jax.random.fold_in(KEY, k), (k,))
        bi = jax.random.normal(jax.random.fold_in(KEY, k + 1), (k,))
        xin = ref.pad_same(x, 3, 3) if algo == "depthwise" else x
        want = ref.apply_epilogue(gt, scale=sc, bias=bi, act="relu6")
        y = ops.ALGORITHMS[algo](xin, w, impl="pallas", scale=sc, bias=bi,
                                 act="relu6")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=1e-3, err_msg=algo)


def test_conv2d_fused_epilogue_strided():
    """The conv2d entry point threads (scale, bias, act) through dispatch
    at strided sites too — the stem's conv+BN+ReLU in one call."""
    x, wgt = _mk(1, 32, 32, 3, 64, r=7, s=7)
    sc = jax.random.normal(jax.random.fold_in(KEY, 21), (64,))
    bi = jax.random.normal(jax.random.fold_in(KEY, 22), (64,))
    want = ref.apply_epilogue(ref.conv2d_reference(x, wgt, stride=2),
                              scale=sc, bias=bi, act="relu")
    for algo in ("auto", "ilpm", "direct", "xla"):
        y = conv2d(x, wgt, stride=2, algorithm=algo, scale=sc, bias=bi,
                   act="relu")
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-4, atol=1e-3, err_msg=algo)


# ---------------------------------------------------------------------
# depthwise channel multiplier > 1

@pytest.mark.parametrize("mult", [2, 3])
@pytest.mark.parametrize("stride", [1, 2])
def test_depthwise_channel_multiplier_vs_ground_truth(mult, stride):
    c = 10
    x = jax.random.normal(KEY, (1, 11, 13, c))
    wgt = jax.random.normal(jax.random.fold_in(KEY, 9), (3, 3, 1, mult * c))
    gt = ref.conv2d_reference(x, wgt, stride=stride, groups=c)
    xp = ref.pad_same(x, 3, 3, stride=stride)
    for impl in ("pallas", "jnp"):
        y = ops.depthwise(xp, wgt, impl=impl, stride=stride)
        np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-4,
                                   atol=1e-3, err_msg=impl)
    # and through the public entry point (groups detected from shapes)
    y = conv2d(x, wgt, stride=stride, algorithm="auto")
    np.testing.assert_allclose(np.asarray(y), np.asarray(gt), rtol=1e-4,
                               atol=1e-3)


def test_convspec_channel_multiplier():
    x = jax.random.normal(KEY, (1, 8, 8, 12))
    wgt = jax.random.normal(KEY, (3, 3, 1, 24))  # M = 2
    spec = ConvSpec.from_tensors(x, wgt, 1)
    assert (spec.c, spec.k, spec.groups) == (12, 24, 12)
    assert spec.depthwise and spec.channel_multiplier == 2
    assert tunable(spec)
    assert cost_model_select(spec).algorithm == "depthwise"


# ---------------------------------------------------------------------
# whole-backbone coverage + the cached Winograd transform

@pytest.mark.parametrize("net", ["resnet18", "resnet50"])
def test_tuned_resnet_plan_has_no_xla_dense_sites(net):
    """Acceptance: a tuned ResNet plan contains zero 'xla' choices — stem,
    strided stage entries, and every 1x1 included — and the fused forward
    matches the unfused all-XLA reference."""
    cfg = tiny_variant(get(net))
    eng = InferenceEngine(cfg)
    algos = eng.plan.algorithms()
    xla_sites = [n for n, a in algos.items() if a == "xla"]
    assert not xla_sites, xla_sites
    # strided + 1x1 sites resolve to real kernel families
    assert algos["stem"] in ("ilpm", "direct")
    assert algos["s1b0.proj"] == "pointwise"
    # block sites resolve to the fused family too — no block ever
    # regresses to an escape hatch (select_block returns None, never xla)
    assert eng.plan.block_choices
    assert all(c.algorithm == "fused_residual_conv"
               for c in eng.plan.block_choices.values())
    img = jax.random.normal(KEY, (32, 32, 3))
    out = eng.run(img)
    want = InferenceEngine(cfg, params=eng.params, algorithm="xla").run(img)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-3,
                               atol=1e-3)


def test_winograd_filter_transform_cached_once_per_engine(monkeypatch):
    """U = G g G^T is computed exactly once per winograd site at engine
    build, never per forward (weights are frozen at inference)."""
    calls = {"n": 0}
    inner = ref.winograd_filter_transform

    def counting(w):
        calls["n"] += 1
        return inner(w)

    monkeypatch.setattr(ref, "winograd_filter_transform", counting)

    cfg = tiny_variant(get("resnet18"))
    # pin one even-sized stride-1 3x3 site to winograd; the engine must
    # transform its filters exactly once at build time
    plan = TuningPlan(mode="cost_model")
    plan.specs["s0b0.c1"] = ConvSpec(h=8, w=8, c=64, k=64)
    plan.choices["s0b0.c1"] = Choice("winograd", (), 0.0, 1, 1, 1)
    eng = InferenceEngine(cfg, plan=plan)
    assert calls["n"] == 1
    assert set(eng.winograd_u) == {"s0b0.c1"}

    img = jax.random.normal(KEY, (32, 32, 3))
    out = eng.run(img)
    eng.run(img)
    assert calls["n"] == 1  # forwards reuse the cached U

    want = InferenceEngine(cfg, params=eng.params, algorithm="xla").run(img)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), rtol=1e-3,
                               atol=1e-3)
