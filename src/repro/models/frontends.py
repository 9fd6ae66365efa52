"""Modality frontends.

Per the assignment spec the VLM/audio frontends are STUBS for the assigned
shapes — ``input_specs()`` provides precomputed frame/patch embeddings. The
real conv paths are implemented here anyway (they are where the paper's
technique lives for these archs) and are exercised by unit tests + the conv
benchmarks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.spec import ParamSpec


def vit_patch_specs(cfg, patch=14, in_ch=3):
    return {"w": ParamSpec((patch, patch, in_ch, cfg.d_model),
                           (None, None, None, "embed_fsdp")),
            "b": ParamSpec((cfg.d_model,), (None,), "zeros")}


def patch_conv(images, w, patch):
    """Stride-``patch`` VALID ``patch``x``patch`` conv, (B,H,W,C) ->
    (B, H//patch, W//patch, K): non-overlapping windows make it the
    degenerate ILP-M case — a single "tap block", i.e. reshape + matmul,
    K on lanes."""
    B, H, W, C = images.shape
    K = w.shape[-1]
    hp, wp = H // patch, W // patch
    xr = images[:, :hp * patch, :wp * patch].reshape(
        B, hp, patch, wp, patch, C).transpose(0, 1, 3, 2, 4, 5)
    xr = xr.reshape(B, hp * wp, patch * patch * C)
    y = jnp.einsum("bpc,ck->bpk", xr, w.reshape(-1, K))
    return y.reshape(B, hp, wp, K)


def vit_patch_embed(p, cfg, images, patch=14):
    """images: (B,H,W,3) -> (B, n_patches, d_model) via the stride-`patch`
    conv ``patch_conv``."""
    y = patch_conv(images, p["w"], patch)
    B, Hp, Wp, C = y.shape
    return (y + p["b"]).reshape(B, Hp * Wp, C)


def audio_stem_specs(cfg, n_mels=80):
    return {
        "w1": ParamSpec((3, n_mels, cfg.d_model), (None, None, "embed_fsdp")),
        "b1": ParamSpec((cfg.d_model,), (None,), "zeros"),
        "w2": ParamSpec((3, cfg.d_model, cfg.d_model), (None, None, "embed_fsdp")),
        "b2": ParamSpec((cfg.d_model,), (None,), "zeros"),
    }


def audio_stem(p, cfg, mel):
    """mel: (B, T, n_mels) -> (B, T//2, d_model): whisper's 2-conv stem.

    conv1: k=3 stride 1; conv2: k=3 stride 2; GELU after each. Implemented
    with the ILP-M layout (channels-last, taps accumulated) — the 1D
    specialization of the paper's algorithm.
    """
    from repro.kernels import ops as kops

    x = jax.nn.gelu(kops.conv1d_dense(mel, p["w1"], p["b1"], stride=1))
    x = jax.nn.gelu(kops.conv1d_dense(x, p["w2"], p["b2"], stride=2))
    return x
