"""Tiny variants of the benchmark's configurations for the CPU tests, and
the path set-up every test file of the benchmark needs."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

NETWORKS = {"resnet18-224-fp32": "resnet18",
            "mobilenet_v2-224-fp32": "mobilenet_v2"}


def tiny(config):
    """(bench Model, program cfg) at the sizes of ``tiny_variant``: 32x32
    images, 256 classes, one block per stage (ResNet) or four short rows
    (MobileNetV2)."""
    from benchlib.model import Model
    from repro.configs import get, tiny_variant

    pcfg = tiny_variant(get(NETWORKS[config]))
    cfg = dict(Model(config).cfg)
    cfg["image"] = [pcfg.extra["img"], pcfg.extra["img"], 3]
    cfg["classes"] = pcfg.vocab_size
    if "stages" in cfg:
        cfg["stages"] = [dict(s, blocks=b) for s, b in
                         zip(cfg["stages"], pcfg.extra["blocks"])]
    else:
        cfg["settings"] = [list(r) for r in pcfg.extra["settings"]]
        cfg["stem"] = dict(cfg["stem"], width=pcfg.extra["stem"])
        cfg["head"] = pcfg.extra["head"]
    return Model(config, cfg=cfg), pcfg


def tiny_cell(workload, config):
    from benchlib import harness

    cell = harness.load_cell(workload)
    cell.model, pcfg = tiny(config)
    return cell, pcfg
