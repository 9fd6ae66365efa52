"""Plain jax.numpy building blocks of the reference forwards.

Nothing here comes from the system under test. Each op is the textbook
definition: a convolution through ``lax.conv_general_dilated`` with TF-style
SAME padding, folded batch norm as a per-channel scale and bias, ReLU or
ReLU6, a residual add, a 3x3/2 max-pool, global average pooling and a dense
classifier.

Every forward computes in float32 with every product at HIGHEST precision;
``Precision`` says where values are rounded to bfloat16 on the way, with
``lax.reduce_precision``, which XLA keeps (a bfloat16 dtype alone lets XLA
keep float32 inside a fusion and skip the rounding):

* ``REFERENCE``: nowhere. The plain reference.
* ``STATED``: the operands of every convolution and of the classifier, and
  nothing else: the precision the configurations state, float32 at the
  default matmul precision, which on TPU v5e is one bfloat16 pass per
  product, accumulated and stored in float32.
* ``CONTROL``: the operands, and every value stored between ops (each
  convolution's output, each batch norm, residual add, pooled feature and
  logit): bfloat16 storage, the nearest precision below the stated one.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax


@dataclass(frozen=True)
class Precision:
    operands: bool  # round each product's operands to bfloat16
    storage: bool   # round every stored value to bfloat16


REFERENCE = Precision(operands=False, storage=False)
STATED = Precision(operands=True, storage=False)
CONTROL = Precision(operands=True, storage=True)


def bf16(x):
    """``x`` rounded to the nearest bfloat16, kept as float32."""
    return lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def store(x, p: Precision):
    return bf16(x) if p.storage else x


def _operand(x, p: Precision):
    return bf16(x) if p.operands or p.storage else x


def conv(x, w, stride, p: Precision, groups=1):
    """NHWC x HWIO -> NHWC, SAME padding."""
    return store(lax.conv_general_dilated(
        _operand(x, p), _operand(w, p), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        feature_group_count=groups, precision=lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32), p)


def bn(y, leaf, p: Precision):
    return store(y * store(leaf["scale"], p) + store(leaf["bias"], p), p)


def act(y, kind):
    if kind == "relu":
        return jnp.maximum(y, 0)
    if kind == "relu6":
        return jnp.clip(y, 0, 6)
    assert kind is None, kind
    return y


def add(a, b, p: Precision):
    """A residual add."""
    return store(a + b, p)


def conv_bn(x, leaf, stride, p: Precision, activation=None, groups=1):
    return act(bn(conv(x, leaf["w"], stride, p, groups), leaf, p), activation)


def max_pool(x, kernel, stride):
    return lax.reduce_window(x, jnp.array(-jnp.inf, x.dtype), lax.max,
                             (1, kernel, kernel, 1), (1, stride, stride, 1),
                             "SAME")


def classify(x, leaf, p: Precision):
    """Global average pool, then the dense classifier."""
    pooled = store(jnp.mean(x, axis=(1, 2)), p)
    logits = store(jnp.dot(_operand(pooled, p), _operand(leaf["w"], p),
                           precision=lax.Precision.HIGHEST,
                           preferred_element_type=jnp.float32), p)
    return store(logits + store(leaf["b"], p), p)


def jit_forward(forward, cfg, p: Precision):
    """One compiled forward ``(params, images) -> float32 logits``."""
    def fwd(params, images):
        return forward(params, store(images.astype(jnp.float32), p), cfg, p)
    return jax.jit(fwd)
