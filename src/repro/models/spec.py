"""Parameter specification trees.

Models declare an *abstract* parameter tree of ``ParamSpec`` leaves (shape +
logical axes + initializer). From one spec tree we derive: real initialized
params (smoke tests / examples), ``jax.ShapeDtypeStruct`` stand-ins (dry-run,
no allocation), and ``NamedSharding`` trees (pjit in/out shardings).
"""
from __future__ import annotations

import zlib
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.sharding.rules import logical_sharding


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple  # logical axis names, len == len(shape)
    init: str = "normal"  # normal | zeros | ones | embed
    scale: float | None = None  # stddev override; default fan-in scaled
    dtype: str | None = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def stack(spec: ParamSpec, n: int) -> ParamSpec:
    """Add a leading stacked-layer dim (consumed by jax.lax.scan)."""
    return ParamSpec((n, *spec.shape), ("layer", *spec.axes), spec.init,
                     spec.scale, spec.dtype)


def stack_tree(tree, n: int):
    return jax.tree.map(lambda s: stack(s, n), tree,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def _fan_in(spec: ParamSpec) -> int:
    if len(spec.shape) == 0:
        return 1
    # convention: last axis is the output axis for 2D+ weights
    fan = int(np.prod(spec.shape[:-1])) if len(spec.shape) > 1 else spec.shape[0]
    return max(fan, 1)


def init_leaf(spec: ParamSpec, key, default_dtype) -> jax.Array:
    dtype = spec.dtype or default_dtype
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "embed":
        return (jax.random.normal(key, spec.shape, jnp.float32) * 0.02).astype(dtype)
    scale = spec.scale if spec.scale is not None else _fan_in(spec) ** -0.5
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(dtype)


def _walk(tree, path=()):
    if isinstance(tree, ParamSpec):
        yield path, tree
    elif isinstance(tree, dict):
        for k in sorted(tree):
            yield from _walk(tree[k], path + (k,))
    else:
        raise TypeError(f"bad spec node at {path}: {type(tree)}")


def init_params(spec_tree, seed: int, param_dtype: str):
    """Materialize a spec tree (CPU-sized configs only).

    Each leaf's key folds a CRC of its path names into ``seed``, so the
    same seed gives the same weights in every process (``hash()`` of a
    string is salted per process)."""
    root = jax.random.key(seed)

    def build(tree):
        if isinstance(tree, ParamSpec):
            return None
        return {k: build(v) for k, v in tree.items()}

    out = build(spec_tree)
    for path, spec in _walk(spec_tree):
        key = root
        for p in path:
            key = jax.random.fold_in(key, zlib.crc32(str(p).encode()) % 2**31)
        node = out
        for p in path[:-1]:
            node = node[p]
        node[path[-1]] = init_leaf(spec, key, param_dtype)
    return out if out is not None else init_leaf(spec_tree, root, param_dtype)


def abstract_params(spec_tree, param_dtype: str):
    """ShapeDtypeStruct tree — dry-run stand-ins, zero allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype or param_dtype)),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def param_shardings(spec_tree, mesh, rules):
    return jax.tree.map(
        lambda s: logical_sharding(s.axes, s.shape, rules, mesh),
        spec_tree, is_leaf=lambda x: isinstance(x, ParamSpec))


def count(spec_tree) -> int:
    return sum(int(np.prod(s.shape)) for _, s in _walk(spec_tree))
