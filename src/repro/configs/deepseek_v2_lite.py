"""deepseek-v2-lite [moe, mla]: 27L, d=2048, 16H latent attention
(kv_lora_rank 512, no q LoRA, qk 128 + 64 rope, v 128, YaRN factor 40),
first layer dense (10944), then 64 routed experts of 1408, top-6 softmax
unnormalised (``routed_scaling_factor`` 1), 2 shared; RMSNorm eps 1e-6;
vocab 102400, untied head.
[https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/config.json]

It runs on the gossip-FL trainer's own model (``models/mla.py``), not
through ``build_model``."""

from repro.models.common import ModelConfig

ARCH_ID = "deepseek-v2-lite"


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID,
        family="moe",
        num_layers=27,
        d_model=2048,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,
        vocab_size=102400,
        max_seq_len=163840,
        rope_theta=10000.0,
        num_experts=64,
        num_experts_per_tok=6,
        norm_topk_prob=False,
        moe_d_ff=1408,
        num_shared_experts=2,
        first_k_dense=1,
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_yarn=(40.0, 4096, 32.0, 1.0, 0.707, 0.707),
    )


def smoke_config() -> ModelConfig:
    """Same family, CPU-test widths: 3 layers (dense + 2 expert layers), 16
    experts top-4 of which 8 held, YaRN kept (a factor of 4 over 64
    positions)."""
    return config().replace(
        num_layers=3, d_model=64, num_heads=4, num_kv_heads=4, d_ff=96,
        vocab_size=256, max_seq_len=256, num_experts=16, num_experts_per_tok=4,
        moe_d_ff=32, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=12, rope_yarn=(4.0, 64, 32.0, 1.0, 0.707, 0.707),
        experts_held=8,
    )
