"""Reduced same-family configs for CPU smoke tests.

Each assigned arch gets a faithful miniature: same family/block plan
(GQA ratios, MLA latents, MoE routing, hybrid interleave, enc-dec split),
small widths/depths/vocab so one fwd/train step runs on a single CPU device.
"""
from repro.configs.base import ArchConfig


def tiny_variant(cfg: ArchConfig) -> ArchConfig:
    """Derive the reduced smoke config of the same family."""
    kw: dict = dict(
        name=cfg.name + "-tiny",
        dtype="float32",
        param_dtype="float32",
        remat="none",
        vocab_size=min(cfg.vocab_size, 256) or 256,
        attn_chunk=64,
    )
    if cfg.family == "cnn":
        extra = {**cfg.extra, "img": 32}
        if "blocks" in extra:  # resnet family
            extra["blocks"] = (1, 1, 1, 1)
        if "settings" in extra:  # mobilenet family: one block per stage,
            # keeping the structural variety (t=1 stage, strided stages,
            # a residual-eligible stride-1 stage)
            extra.update(settings=((1, 16, 1, 1), (6, 24, 1, 2),
                                   (6, 24, 1, 1), (6, 40, 1, 2)),
                         stem=16, head=64)
        if "rows" in extra:  # efficientnet: one block per row, keeping a
            # t=1 row, 5x5 rows at stride 2 and at stride 1 (the latter
            # residual), a 3x3 stride-2 row, and SE in every block
            extra.update(rows=((1, 3, 1, 16, 1), (6, 5, 2, 24, 1),
                               (6, 5, 1, 24, 1), (6, 3, 2, 40, 1)),
                         stem=16, head=64)
        return cfg.replace(**{**kw, "extra": extra})

    if cfg.attn_impl == "mla":
        kw.update(num_heads=4, num_kv_heads=4, kv_lora_rank=32, q_lora_rank=48,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, head_dim=16)
    elif cfg.attn_impl == "gqa":
        ratio = max(1, cfg.num_heads // max(cfg.num_kv_heads, 1))
        kw.update(num_heads=4, num_kv_heads=max(1, 4 // min(ratio, 4)), head_dim=16)

    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state=16, ssm_head_dim=16, ssm_ngroups=min(cfg.ssm_ngroups, 2),
                  ssd_chunk=16)

    if cfg.num_experts:
        kw.update(num_experts=8, top_k=min(cfg.top_k, 2),
                  num_shared_experts=min(cfg.num_shared_experts, 1),
                  moe_d_ff=64)

    if cfg.family == "hybrid":
        # one full interleave period + change-of-period coverage
        kw.update(num_layers=cfg.attn_layer_period,
                  attn_layer_offset=min(cfg.attn_layer_offset, cfg.attn_layer_period - 1))
    elif cfg.is_encoder_decoder:
        kw.update(num_layers=2, num_encoder_layers=2, encoder_seq=16, frontend_tokens=16)
    else:
        kw.update(num_layers=2 + cfg.first_dense_layers)

    kw.update(d_model=64, d_ff=128 if cfg.d_ff else 0)
    if cfg.frontend == "vit_stub":
        kw.update(frontend_tokens=8)
    return cfg.replace(**kw)
