"""The layer tables against the served networks' own accounting, at the
published sizes."""
import math

import pytest

import bench_tiny  # noqa: F401  (paths)
from benchlib.model import Model, conv_flops

CASES = [("resnet18-224-fp32", "resnet18", 3_627_122_688, 46_758_048),
         ("mobilenet_v2-224-fp32", "mobilenet_v2", 598_988_544, 14_019_488)]


@pytest.mark.parametrize("config,network,conv_flops_sum,weight_bytes", CASES)
def test_layer_table_flops_and_bytes_match_the_program(
        config, network, conv_flops_sum, weight_bytes):
    from repro.configs import get
    from repro.models.registry import cnn_module

    model = Model(config)
    cfg = get(network)
    specs = cnn_module(cfg).conv_specs(cfg)
    ours = [r for r in model.rows if r["op"] == "conv"]
    assert sum(s.flops for _, s in specs) == conv_flops_sum
    assert sum(conv_flops(r) for r in ours) == conv_flops_sum
    assert [r["name"] for r in ours] == [n for n, _ in specs]
    fc = [r for r in model.rows if r["op"] == "fc"]
    assert model.flops_per_image == conv_flops_sum + 2 * fc[0]["cin"] * 1000
    leaves = []

    def walk(tree):
        for v in tree.values():
            walk(v) if isinstance(v, dict) else leaves.append(v.shape)
    walk(cnn_module(cfg).model_specs(cfg))
    assert 4 * sum(math.prod(s) for s in leaves) == weight_bytes
    assert model.weight_bytes == weight_bytes


@pytest.mark.parametrize("config", [c[0] for c in CASES])
def test_published_parameter_counts(config):
    model = Model(config)
    assert model.weight_bytes // 4 == model.cfg["published"]["params"]


def test_weights_come_from_the_seed_alone():
    import numpy as np

    model, _ = bench_tiny.tiny("resnet18-224-fp32")
    big = 2**31 + 12345
    a, b, c = (model.init_params(s) for s in (big, big, big + 1))
    wa, wb, wc = (np.asarray(t["s0b0"]["c1"]["w"]) for t in (a, b, c))
    assert np.array_equal(wa, wb) and not np.array_equal(wa, wc)
    assert np.asarray(a["s0b0"]["c1"]["scale"]).std() > 0
