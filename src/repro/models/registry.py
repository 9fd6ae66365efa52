"""Model registry: per-family dispatch + analytic parameter counting."""
from __future__ import annotations

import numpy as np

from repro.models import encdec, lm, mobilenet, resnet
from repro.models import spec as pspec


def cnn_module(cfg):
    """The CNN family module (forward / model_specs / conv_specs) for a
    config — ``extra["arch"]`` routes: MobileNetV2 and EfficientNet are
    the inverted-residual family; ResNet is the default."""
    if cfg.extra.get("arch") in ("mobilenet", "efficientnet"):
        return mobilenet
    return resnet


def model_specs(cfg):
    if cfg.family == "cnn":
        return cnn_module(cfg).model_specs(cfg)
    if cfg.is_encoder_decoder:
        return encdec.model_specs(cfg)
    return lm.model_specs(cfg)


def forward_fn(cfg):
    if cfg.family == "cnn":
        return cnn_module(cfg).forward
    if cfg.is_encoder_decoder:
        return encdec.forward
    return lm.forward


def cache_struct(cfg, batch, max_seq):
    if cfg.is_encoder_decoder:
        return encdec.cache_struct(cfg, batch, max_seq)
    return lm.cache_struct(cfg, batch, max_seq)


def count_params(cfg, active_only: bool = False) -> int:
    """Parameter count from the spec tree; `active_only` counts only the
    routed experts a token actually visits (MODEL_FLOPS for MoE)."""
    if cfg.family == "cnn":
        return pspec.count(cnn_module(cfg).model_specs(cfg))
    tree = model_specs(cfg)
    total = pspec.count(tree)
    if active_only and cfg.num_experts:
        per_expert = cfg.d_model * cfg.moe_d_ff * 3
        n_moe_layers = sum(1 for _, f in lm.layer_plan(cfg) if f == "moe")
        total -= (cfg.num_experts - cfg.top_k) * per_expert * n_moe_layers
    return total
