"""The main path's Pallas kernels compile for a TPU v5e, at published widths.

Nothing here runs on a chip: each test compiles one kernel for a v5e that
is *described* (``jax.experimental.topologies``), which raises whatever the
chip's compiler (Mosaic) would raise — misaligned slices, lowerings it does
not implement, too much VMEM. Interpret mode, which the other kernel tests
use, can show none of that.

The shapes are sites of ResNet-18 and MobileNetV2 at 224x224, with the
tuning parameters the cost model picks there. The strided ones include
every pattern Mosaic refused before the kernels read strided taps from
stride phases (``repro.kernels.phases``): a strided read of a tile wider
than 128 lanes, and a strided slice of a loaded value.

The topology is described inside a fixture, never at import, and the file
stays one file: only one process at a time may load the TPU compiler, so
the one worker that runs these tests is the only one that loads it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import depthwise_conv, fused_block, ilpm_conv, pointwise_conv

F32 = jnp.float32


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compilation cache off:
    what is compiled for a described chip cannot be read back here."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_conv(fn, chip, x_shape, w_shape, **static):
    """Compile a conv kernel with a folded-BN epilogue for the described
    chip; returns its HLO text."""
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, F32, sharding=chip)

    k = w_shape[-1]
    return fn.lower(sds(x_shape), sds(w_shape), scale=sds((k,)),
                    bias=sds((k,)), **static,
                    interpret=False).compile().as_text()


def _padded(h, r, stride):
    """Padded input extent of a SAME conv with output ceil(h/stride)."""
    return (-(-h // stride) - 1) * stride + r


# (H, C, K, R, stride, block_k): ResNet-18 dense sites
DENSE = [
    (224, 3, 64, 7, 2, 64),      # stem 7x7/2 (folded: 4x4/1 over C=12)
    (56, 64, 64, 3, 1, 64),      # s0b0.c1
    (56, 64, 128, 3, 2, 128),    # s1b0.c1
    (14, 256, 512, 3, 2, 128),   # s3b0.c1: strided, C > 128 lanes
]


@pytest.mark.parametrize("h,c,k,r,stride,block_k", DENSE,
                         ids=[f"{h}x{h}x{c}-{k}_{r}x{r}s{s}"
                              for h, c, k, r, s, _ in DENSE])
def test_ilpm_compiles(chip, h, c, k, r, stride, block_k):
    hp = _padded(h, r, stride)
    hlo = _compile_conv(ilpm_conv.ilpm_conv, chip, (1, hp, hp, c),
                        (r, r, c, k), stride=stride, block_k=block_k,
                        act="relu")
    assert "tpu_custom_call" in hlo


# (H, C, K, stride): 1x1 sites — ResNet-18 projection shortcuts, the
# MobileNetV2 head
POINTWISE = [(56, 64, 128, 2), (14, 256, 512, 2), (7, 320, 1280, 1)]


@pytest.mark.parametrize("h,c,k,stride", POINTWISE,
                         ids=[f"{h}x{h}x{c}-{k}_s{s}"
                              for h, c, k, s in POINTWISE])
def test_pointwise_compiles(chip, h, c, k, stride):
    hlo = _compile_conv(pointwise_conv.pointwise_conv, chip, (1, h, h, c),
                        (1, 1, c, k), stride=stride, block_k=128)
    assert "tpu_custom_call" in hlo


# (H, C): MobileNetV2's stride-2 depthwise sites s1b0.dw and s2b0.dw
DEPTHWISE = [(112, 96), (56, 144)]


@pytest.mark.parametrize("h,c", DEPTHWISE,
                         ids=[f"{h}x{h}x{c}_s2" for h, c in DEPTHWISE])
def test_depthwise_stride2_compiles(chip, h, c):
    hp = _padded(h, 3, 2)
    hlo = _compile_conv(depthwise_conv.depthwise_conv, chip, (1, hp, hp, c),
                        (3, 3, 1, c), stride=2, block_c=128, act="relu6")
    assert "tpu_custom_call" in hlo


def test_fused_residual_conv_compiles(chip):
    """ResNet-18 s0b0: the block's second conv with the shortcut add."""
    fn = jax.jit(lambda x, w, s, b, res: fused_block.fused_residual_conv(
        x, {"w": w, "scale": s, "bias": b}, res=res, block_k=64,
        interpret=False))
    args = [jax.ShapeDtypeStruct(s, F32, sharding=chip) for s in
            [(1, 58, 58, 64), (3, 3, 64, 64), (64,), (64,), (1, 56, 56, 64)]]
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()


# (H, Cin, mid, Cout, stride): MobileNetV2 s1b1 (residual) and s2b0
INVERTED = [(56, 24, 144, 24, 1), (56, 24, 144, 32, 2)]


@pytest.mark.parametrize("h,cin,mid,cout,stride", INVERTED,
                         ids=[f"{h}x{h}_{ci}-{m}-{co}_s{s}"
                              for h, ci, m, co, s in INVERTED])
def test_fused_inverted_residual_compiles(chip, h, cin, mid, cout, stride):
    shapes = {"w1": (1, 1, cin, mid), "s1": (mid,), "b1": (mid,),
              "wdw": (3, 3, 1, mid), "sdw": (mid,), "bdw": (mid,),
              "w2": (1, 1, mid, cout), "s2": (cout,), "b2": (cout,)}
    fn = jax.jit(lambda x, w: fused_block.fused_inverted_residual(
        x, w, stride=stride, block_m=mid, residual=stride == 1,
        interpret=False))
    x = jax.ShapeDtypeStruct((1, h, h, cin), F32, sharding=chip)
    w = {n: jax.ShapeDtypeStruct(s, F32, sharding=chip)
         for n, s in shapes.items()}
    assert "tpu_custom_call" in fn.lower(x, w).compile().as_text()
