"""Tuning-plan subsystem tests: JSON round-trip, per-layer dispatch,
cost-model vs measured agreement, and whole-package import health."""
import importlib
import pkgutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get, tiny_variant
from repro.core import InferenceEngine, TuningPlan, build_plan, conv2d, select
from repro.core.autotune import (Choice, ConvSpec, cost_model_select,
                                 measured_select, route)
from conftest import spy_algorithms as _spy_algorithms
from repro.kernels import ops

KEY = jax.random.key(0)


def test_plan_json_roundtrip(tmp_path):
    specs = [("a", ConvSpec(h=8, w=8, c=16, k=16)),
             ("b", ConvSpec(h=4, w=4, c=32, k=32)),
             ("stem", ConvSpec(h=32, w=32, c=3, k=64, r=7, s=7, stride=2)),
             # grouped sites: depthwise (strided) + pointwise 1x1
             ("dw", ConvSpec(h=8, w=8, c=32, k=32, groups=32, stride=2)),
             ("pw", ConvSpec(h=8, w=8, c=32, k=64, r=1, s=1))]
    plan = build_plan(specs, mode="cost_model")
    back = TuningPlan.from_json(plan.to_json())
    assert back.mode == plan.mode
    assert back.specs == plan.specs
    assert back.choices == plan.choices

    path = tmp_path / "plan.json"
    plan.save(path)
    loaded = TuningPlan.load(path)
    assert loaded.choices == plan.choices
    assert loaded.specs == plan.specs


def test_plan_rejects_unknown_version():
    with pytest.raises(ValueError):
        TuningPlan.from_json('{"version": 999, "mode": "x", "layers": {}}')


def test_per_layer_dispatch_reaches_chosen_kernels(monkeypatch):
    """Two layers pinned to *different* algorithms with explicit kernel
    params must each reach their own kernel — the per-layer dispatch the
    engine's plan threading exists to provide."""
    cfg = tiny_variant(get("resnet18"))
    plan = TuningPlan(mode="cost_model")
    plan.specs["s0b0.c1"] = ConvSpec(h=8, w=8, c=64, k=64)
    plan.choices["s0b0.c1"] = Choice("direct", (("block_h", 4),), 0.0, 1, 1, 1)
    plan.specs["s0b0.c2"] = ConvSpec(h=8, w=8, c=64, k=64)
    plan.choices["s0b0.c2"] = Choice("ilpm", (("block_k", 64),), 0.0, 1, 1, 1)

    calls = _spy_algorithms(monkeypatch)
    eng = InferenceEngine(cfg, plan=plan)
    eng.run(jax.random.normal(KEY, (32, 32, 3)))
    assert ("direct", (("block_h", 4),)) in calls
    assert ("ilpm", (("block_k", 64),)) in calls


def test_engine_auto_plan_drives_dispatch(monkeypatch, tmp_path):
    """algorithm='auto' jits a forward where each layer runs its tuned
    algorithm with its tuned params, and the plan survives save/load."""
    cfg = tiny_variant(get("resnet18"))
    eng = InferenceEngine(cfg)  # algorithm="auto": builds a plan
    plan = eng.plan
    assert plan is not None

    # the plan is genuinely per-layer: >= 2 distinct algorithms (3x3 sites
    # pick a dense kernel, 1x1 projections pick pointwise), and the tuned
    # kernel params differ across layers (block_k tracks K)
    assert len(set(plan.algorithms().values())) >= 2
    tuned = {n: c for n, c in plan.choices.items() if c.algorithm != "xla"}
    assert len(tuned) >= 2
    assert len({c.params for c in tuned.values()}) >= 2
    # the tuner fuses the residual add into each block's final conv
    assert plan.block_choices
    assert all(c.algorithm == "fused_residual_conv"
               for c in plan.block_choices.values())

    calls = _spy_algorithms(monkeypatch)
    img = jax.random.normal(KEY, (32, 32, 3))
    logits = eng.run(img)

    # the dispatched kernels match the plan exactly: one call per planned
    # non-xla site with that site's tuned params, except that each fused
    # block replaces its final per-conv dispatch with ONE block dispatch
    fused_convs = {f"{b[:-len('.block')]}.{sfx}"
                   for b in plan.block_choices
                   for sfx, _ in plan.block_specs[b].conv_specs()}
    expected = sorted(
        [(c.algorithm, c.params) for n, c in tuned.items()
         if n not in fused_convs]
        + [(c.algorithm, c.params) for c in plan.block_choices.values()])
    assert sorted(calls) == expected

    # tune-once / deploy-many: JSON round-trip, same dispatch, same logits
    path = tmp_path / "plan.json"
    eng.save_plan(path)
    loaded = TuningPlan.load(path)
    assert loaded.choices == plan.choices
    assert loaded.block_choices == plan.block_choices
    assert loaded.block_specs == plan.block_specs

    calls.clear()
    eng2 = InferenceEngine(cfg, params=eng.params, plan=str(path))
    logits2 = eng2.run(img)
    assert sorted(calls) == expected
    np.testing.assert_allclose(np.asarray(logits2), np.asarray(logits),
                               rtol=1e-6, atol=1e-6)


DENSE = dict(h=8, w=8, c=8, k=16)
DW = dict(h=8, w=8, c=8, k=8, groups=8)


@pytest.mark.parametrize("spec_kw, algorithm, want", [
    (DENSE, "auto", "ilpm"),
    (DENSE, "xla", "xla"),
    (DENSE, "direct", "direct"),
    (DENSE, "im2col", "im2col"),
    ({**DENSE, "stride": 2}, "direct", "direct"),
    (DENSE, "pointwise", "ilpm"),
    ({**DENSE, "r": 1, "s": 1, "stride": 2}, "pointwise", "pointwise"),
    ({**DENSE, "stride": 2}, "im2col", "ilpm"),
    ({**DENSE, "stride": 2}, "libdnn", "ilpm"),
    ({**DENSE, "stride": 2}, "winograd", "ilpm"),
    (DENSE, "winograd", "winograd"),
    ({**DENSE, "h": 7, "w": 7}, "winograd", "ilpm"),
    ({**DENSE, "r": 5, "s": 5}, "winograd", "ilpm"),
    ({**DW, "stride": 2}, "depthwise", "depthwise"),
    (DW, "ilpm", "xla"),
    (DW, "pointwise", "xla"),
    ({**DENSE, "groups": 4}, "depthwise", "xla"),
    ({**DW, "h": 9, "w": 9, "stride": 3}, "depthwise", "xla"),
], ids=["auto", "xla", "dense_kept", "dense_kept_im2col",
        "dense_kept_strided", "pointwise_on_3x3", "pointwise_strided_1x1",
        "strided_im2col", "strided_libdnn", "strided_winograd",
        "winograd_even", "winograd_odd_output", "winograd_on_5x5",
        "depthwise_kept", "depthwise_asked_as_ilpm",
        "depthwise_asked_as_pointwise", "grouped_non_depthwise",
        "depthwise_at_stride_3"])
def test_route(monkeypatch, spec_kw, algorithm, want):
    """``route`` maps each request to the kernel that fits the site, and
    ``conv2d(algorithm=...)`` runs exactly that kernel, with the kernel's
    default parameters when it was named outright."""
    spec = ConvSpec(**spec_kw)
    choice = route(spec, algorithm)
    assert choice.algorithm == want
    if algorithm == "auto":
        assert choice == select(spec)
    elif want != "xla":
        assert choice.params == ()
    calls = _spy_algorithms(monkeypatch)
    x = jax.random.normal(KEY, (1, spec.h, spec.w, spec.c))
    w = jax.random.normal(KEY, (spec.r, spec.s, spec.c_per_group, spec.k))
    conv2d(x, w, stride=spec.stride, algorithm=algorithm)
    assert calls == ([] if want == "xla" else [(want, choice.params)])


@pytest.mark.parametrize("net", ["resnet18", "mobilenet_v2",
                                 "efficientnet_b0"])
def test_tuned_forward_runs_each_planned_site_once(monkeypatch, net):
    """The tuned forward makes exactly one kernel call per planned conv or
    block site, with the plan's algorithm and parameters, and an engine
    forced onto ``xla`` makes none."""
    cfg = tiny_variant(get(net))
    eng = InferenceEngine(cfg)
    plan = eng.plan
    fused = {f"{b[:-len('.block')]}.{sfx}" for b in plan.block_choices
             for sfx, _ in plan.block_specs[b].conv_specs()}
    want = sorted([(c.algorithm, c.params) for n, c in plan.choices.items()
                   if n not in fused]
                  + [(c.algorithm, c.params)
                     for c in plan.block_choices.values()])
    calls = _spy_algorithms(monkeypatch)
    img = jax.random.normal(KEY, (32, 32, 3))
    eng.run(img)
    assert sorted(calls) == want
    assert len(calls) == len(plan.choices) - len(fused) \
        + len(plan.block_choices)

    calls.clear()
    xla = InferenceEngine(cfg, params=eng.params, algorithm="xla")
    assert set(xla.plan.algorithms().values()) == {"xla"}
    assert not xla.plan.block_choices
    xla.run(img)
    assert calls == []


def test_plan_validation_rejects_wrong_network(tmp_path):
    """A plan tuned for one input size must not silently deploy onto a
    network with different conv geometry."""
    cfg = tiny_variant(get("resnet18"))
    eng = InferenceEngine(cfg)
    path = tmp_path / "plan.json"
    eng.save_plan(path)
    full = get("resnet18")  # img=224: same layer names, different shapes
    with pytest.raises(ValueError, match="different network"):
        InferenceEngine(full, params=eng.params, plan=str(path))


def test_bottleneck_plan_sites_and_widths():
    """Bottleneck stages tune their 3x3 at the bottleneck width (cout/4)
    and every 1x1 (reduce/expand/projection) is a planned pointwise site —
    the spec enumeration walks the real geometry."""
    cfg = tiny_variant(get("resnet50"))
    eng = InferenceEngine(cfg)
    plan = eng.plan
    assert set(plan.specs) == {"stem"} | {
        f"s{si}b0.{c}" for si in range(4)
        for c in ("proj", "c1", "c2", "c3")}
    assert (plan.specs["s0b0.c2"].c, plan.specs["s0b0.c2"].k) == (64, 64)
    assert (plan.specs["s3b0.c2"].c, plan.specs["s3b0.c2"].k) == (512, 512)
    assert plan.specs["s1b0.c2"].stride == 2  # stage entry carries stride
    # 1x1 sites: reduce/expand widths and the strided projection shortcut
    assert (plan.specs["s0b0.c1"].c, plan.specs["s0b0.c1"].k) == (64, 64)
    assert (plan.specs["s0b0.c3"].c, plan.specs["s0b0.c3"].k) == (64, 256)
    assert plan.specs["s1b0.proj"].stride == 2
    assert plan.choices["s0b0.c1"].algorithm == "pointwise"
    assert plan.choices["s1b0.proj"].algorithm == "pointwise"
    logits = eng.run(jax.random.normal(KEY, (32, 32, 3)))
    assert logits.shape == (cfg.vocab_size,)
    assert not bool(jnp.isnan(logits).any())


def test_cost_model_and_measured_agree_on_small_spec():
    """Both tuning modes reach the paper's conclusion (ILP-M) on a layer
    big enough that real work, not interpreter dispatch, dominates."""
    spec = ConvSpec(h=32, w=32, c=128, k=128)
    cm = cost_model_select(spec)
    ms = measured_select(spec, repeats=5)
    assert cm.algorithm == ms.algorithm == "ilpm"


def test_measured_select_warns_on_failed_candidate(monkeypatch, caplog):
    import logging

    def boom(x, w, *, impl="auto", **params):
        raise RuntimeError("kaboom")

    monkeypatch.setitem(ops.ALGORITHMS, "im2col", boom)
    with caplog.at_level(logging.WARNING, logger="repro.core.autotune"):
        ch = measured_select(ConvSpec(h=4, w=4, c=4, k=4), repeats=1)
    assert ch.algorithm != "im2col"
    assert "im2col" in caplog.text


def test_import_every_repro_module():
    """Regression net for API drift (e.g. jax.shard_map moving): every
    module in the package must import cleanly."""
    import repro

    failures = []
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        try:
            importlib.import_module(mod.name)
        except Exception as e:  # pragma: no cover - failure path
            failures.append((mod.name, repr(e)))
    assert not failures, failures
