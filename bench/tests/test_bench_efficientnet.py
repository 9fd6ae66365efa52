"""EfficientNet-B0's configuration against the served network, and its SE
kernel families' costs and readers."""
import math
import time

import pytest

import bench_tiny  # noqa: F401  (paths)
from benchlib import families, harness
from benchlib.model import Model, conv_flops
from benchlib.trace import Event, Trace

CONFIG = "efficientnet_b0-224-fp32"


def tiny():
    """(bench Model, program cfg) at the sizes of ``tiny_variant``: 32x32
    images, 256 classes, four one-block rows (a 5x5 row at stride 2 and
    one at stride 1, a 3x3 one at stride 2), SE in every block."""
    from repro.configs import get, tiny_variant

    pcfg = tiny_variant(get("efficientnet_b0"))
    cfg = dict(Model(CONFIG).cfg)
    cfg["image"] = [pcfg.extra["img"], pcfg.extra["img"], 3]
    cfg["classes"] = pcfg.vocab_size
    cfg["rows"] = [list(r) for r in pcfg.extra["rows"]]
    cfg["stem"] = dict(cfg["stem"], width=pcfg.extra["stem"])
    cfg["head"] = pcfg.extra["head"]
    return Model(CONFIG, cfg=cfg), pcfg


def run_tiny(workload="efficientnet_b0.c16", seconds=0.3, **kw):
    cell = harness.load_cell(workload)
    cell.model, pcfg = tiny()
    return harness.run_cell(cell, 2**31 + 4242, seconds, False,
                            time.perf_counter(), program_cfg=pcfg, **kw)


@pytest.mark.parametrize("kernels", ["jnp", "pallas_interpret"])
def test_served_tiny_efficientnet_matches_the_reference(kernels,
                                                        monkeypatch):
    """Every answer of the tiny served network, through ``Server`` and
    batches from the closed16 mix, against the plain reference at HIGHEST
    on the seed's weights. Both sides compute in float32 on the CPU, so
    they differ only by the order of float32 sums (about 1e-6 of the logit
    range); 1e-4 leaves room for that and is far below what a wrong tap,
    gate or epilogue moves (tenths)."""
    from repro.kernels import ops

    if kernels == "pallas_interpret":
        monkeypatch.setattr(ops, "_use_pallas", lambda impl: impl != "jnp")
    result, checks, notes = run_tiny()
    assert result["attempted"] > 0 and result["correct"] is True
    assert checks["gap_highest"]["value"] < 1e-4
    assert max(notes["warm_dispatches"]) == 8  # batches of 8 were served


def test_a_misrouted_answer_is_not_correct():
    import calibrate

    result, checks, _ = run_tiny(on_program=calibrate.misroute)
    assert result["correct"] is False
    assert checks["gap_highest"]["value"] > checks["gap_highest"]["limit"]


def test_layer_table_matches_the_program_at_published_size():
    """FLOPs, weight bytes and parameter count of the layer table against
    the served network's own ``model_specs`` and ``conv_specs``."""
    from repro.configs import get
    from repro.models.registry import cnn_module

    model = Model(CONFIG)
    cfg = get("efficientnet_b0")
    specs = cnn_module(cfg).conv_specs(cfg)
    convs = [r for r in model.rows if r["op"] == "conv"]
    assert [r["name"] for r in convs] == [n for n, _ in specs]
    assert [sum(s.flops for _, s in specs)] \
        == [sum(conv_flops(r) for r in convs)] == [767_815_104]
    for row, (_, spec) in zip(convs, specs):
        assert (bool(row.get("pool")), bool(row.get("gated"))) \
            == (spec.pool, spec.gated)
    leaves = []

    def walk(tree):
        for v in tree.values():
            walk(v) if isinstance(v, dict) else leaves.append(v.shape)
    walk(cnn_module(cfg).model_specs(cfg))
    assert sum(math.prod(s) for s in leaves) == 5_288_548
    assert model.weight_bytes == 4 * 5_288_548
    assert model.weight_bytes // 4 == model.cfg["published"]["params"]
    # 0.386 GMAC an image: convs, the 32 SE layers and the classifier
    assert model.flops_per_image == 2 * 385_814_752


def test_family_costs_match_the_programs_se_sites():
    """The SE families' rows and FLOPs from the layer table against the
    ConvSpecs of the sites the program runs on those kernels."""
    from repro.configs import get
    from repro.models.registry import cnn_module

    model = Model(CONFIG)
    cfg = get("efficientnet_b0")
    specs = dict(cnn_module(cfg).conv_specs(cfg))
    peaks = harness.peaks_for("TPU v5 lite")
    for family, flag in (("depthwise_pool", "pool"),
                         ("pointwise_gated", "gated")):
        rows = families.rows_of(model, family)
        sites = {n: s for n, s in specs.items() if getattr(s, flag)}
        assert [r["name"] for r in rows] == list(sites)
        assert len(rows) == 16
        flops = sum(s.flops for s in sites.values())
        assert families.least_seconds(model, family, 8.0, peaks) \
            == pytest.approx(8 * flops / peaks["peak_flops"])


def _trace_ctx(ops, runs=2, dispatches=None):
    """A Context over a synthetic window: ``runs`` forward programs, the
    given device operations."""
    tr = Trace((0, 10_000_000), list(ops),
               [Event(i * 4_000_000, i * 4_000_000 + 3_000_000, "forward")
                for i in range(runs)])
    cell = harness.load_cell("efficientnet_b0.c16")
    return harness.Context(cell, harness.peaks_for("TPU v5 lite"), None,
                           0.0, dispatches or {8: runs}, tr)


def test_se_readers_on_a_synthetic_trace():
    ctx = _trace_ctx([Event(0, 3_000_000, "while"),
                      Event(100_000, 1_100_000, "depthwise_pool"),
                      Event(1_200_000, 1_700_000, "pointwise_gated"),
                      Event(4_000_000, 7_000_000, "while")])
    assert harness.reader("se_share.c16").read(ctx) \
        == pytest.approx(100 * 1.5 / 6)
    want = {}
    for family, ns in (("depthwise_pool", 1_000_000),
                       ("pointwise_gated", 500_000)):
        least = families.least_seconds(ctx.cell.model, family, 8.0,
                                       ctx.peaks)
        want[family] = 100 * 2 * least / (ns / 1e9)
        got = harness.reader(f"{family}_roofline.c16").read(ctx)
        assert got == pytest.approx(want[family]) and 0 < got
    # a trace without the SE kernels (a program without them): nothing
    plain = _trace_ctx([Event(0, 3_000_000, "depthwise_conv")])
    for name in ("se_share.c16", "depthwise_pool_roofline.c16",
                 "pointwise_gated_roofline.c16"):
        assert harness.reader(name).read(plain) is None
