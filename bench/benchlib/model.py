"""A configuration as the benchmark runs it: its sizes, its layer table, the
weights drawn from a seed, and its plain reference forward.

A configuration named ``n`` is two files under ``bench/configs/``: ``n.json``
holds the sizes as run, and ``n.py`` holds ``layers(cfg)`` (the layer
table) and ``forward(params, images, cfg, precision)`` (the reference).
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
from functools import cached_property
from pathlib import Path

import jax
import jax.numpy as jnp

from benchlib import refops

CONFIG_DIR = Path(__file__).resolve().parent.parent / "configs"
BYTES = {"float32": 4, "bfloat16": 2}


def load_module(path: Path, name: str):
    """Import a benchmark file by path (its name may hold dots or dashes)."""
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def conv_flops(row) -> int:
    """2 x multiply-adds of one layer-table row, per image."""
    if row["op"] == "fc":
        return 2 * row["cin"] * row["cout"]
    return (2 * row["out_hw"] ** 2 * row["kernel"] ** 2
            * (row["cin"] // row["groups"]) * row["cout"])


def leaf_shapes(row) -> dict:
    if row["op"] == "fc":
        return {"w": (row["cin"], row["cout"]), "b": (row["cout"],)}
    k = row["kernel"]
    return {"w": (k, k, row["cin"] // row["groups"], row["cout"]),
            "scale": (row["cout"],), "bias": (row["cout"],)}


def _leaf(z, kind, shape, gain):
    """Shape a slice of standard normals into one leaf's values: weights
    at variance gain / fan_in (He et al. 2015: gain 2 before a ReLU keeps
    activations at one scale through depth), folded-BN scales near 1,
    biases near 0."""
    z = z.reshape(shape)
    if kind == "w":
        return z * math.sqrt(gain / math.prod(shape[:-1]))
    if kind == "scale":
        return 1.0 + 0.1 * z
    return 0.1 * z  # bias, b


def seed_key(seed: int):
    """A PRNG key for any whole seed, including those past 32 bits."""
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


class Model:
    def __init__(self, name: str, cfg: dict | None = None):
        json_path = CONFIG_DIR / f"{name}.json"
        py_path = CONFIG_DIR / f"{name}.py"
        if not json_path.is_file() or not py_path.is_file():
            raise KeyError(f"no configuration {name!r} in {CONFIG_DIR}")
        self.name = name
        self.cfg = cfg if cfg is not None else json.loads(json_path.read_text())
        self.module = load_module(py_path, "bench_config_" + re.sub(r"\W", "_", name))

    @cached_property
    def rows(self) -> list:
        return self.module.layers(self.cfg)

    @cached_property
    def param_shapes(self) -> dict:
        """{(layer, ..., leaf): shape} in a fixed order."""
        return {path: shape for path, shape, _ in self._leaves}

    @cached_property
    def _leaves(self) -> list:
        """(path, shape, gain) per parameter leaf; gain 2 for the weights
        of a layer followed by an activation, 1 otherwise."""
        return [((*row["name"].split("."), leaf), shape,
                 2.0 if row.get("act") else 1.0)
                for row in self.rows
                for leaf, shape in leaf_shapes(row).items()]

    @property
    def dtype(self):
        return self.cfg["dtype"]

    @cached_property
    def flops_per_image(self) -> int:
        return sum(conv_flops(r) for r in self.rows)

    @cached_property
    def weight_bytes(self) -> int:
        return BYTES[self.dtype] * sum(math.prod(s)
                                       for s in self.param_shapes.values())

    @property
    def image_bytes(self) -> int:
        return BYTES[self.dtype] * math.prod(self.cfg["image"])

    @property
    def logit_bytes(self) -> int:
        return BYTES[self.dtype] * self.cfg["classes"]

    def init_params(self, seed: int):
        """The weights for ``seed``, made on the device in one jitted call
        from one draw of normals, as the nested dict the served network and
        the reference take."""
        leaves = self._leaves
        dtype = jnp.dtype(self.dtype)
        total = sum(math.prod(shape) for _, shape, _ in leaves)

        def make(key):
            z = jax.random.normal(key, (total,), jnp.float32)
            tree, at = {}, 0
            for path, shape, gain in leaves:
                n = math.prod(shape)
                node = tree
                for part in path[:-1]:
                    node = node.setdefault(part, {})
                node[path[-1]] = _leaf(z[at:at + n], path[-1], shape,
                                       gain).astype(dtype)
                at += n
            return tree

        return jax.jit(make)(seed_key(seed))

    def reference(self, precision=refops.REFERENCE):
        """Compiled reference forward ``(params, images) -> f32 logits``."""
        return refops.jit_forward(self.module.forward, self.cfg, precision)
