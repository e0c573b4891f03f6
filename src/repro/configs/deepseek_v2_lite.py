"""deepseek-v2-lite [arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite].

27L d_model=2048 16H MLA (kv_lora=512, no q-LoRA, qk 128+64, v 128) with
YaRN (factor 40 over 4096 positions), MoE 2 shared + 64 routed experts of
1408, top-6 softmax gates not renormalised; first layer dense (d_ff
10944); untied head over 102400 tokens.  Training is dropless: the
assignments are grouped by expert (``MoEConfig.capacity_factor`` 0).
"""
from repro.core.config import (ArchSpec, AttentionConfig, MoEConfig,
                               ModelConfig, RopeScaling, register_arch)

YARN = RopeScaling(factor=40.0, original_max_position_embeddings=4096,
                   beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                   mscale_all_dim=0.707)

FULL = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    num_layers=27,
    d_model=2048,
    d_ff=10944,
    vocab_size=102_400,
    attention=AttentionConfig(
        kind="mla", num_heads=16, num_kv_heads=16, head_dim=128,
        q_lora_rank=0, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=10_000.0, rope_scaling=YARN),
    moe=MoEConfig(num_experts=64, num_experts_per_tok=6,
                  num_shared_experts=2, d_ff_expert=1408, d_ff_shared=2816,
                  first_k_dense=1, d_ff_dense=10944, capacity_factor=0.0,
                  norm_topk_prob=False,
                  aux_loss_coef=0.001),
    act="swiglu",
    norm_eps=1e-6,
    max_seq_len=163_840,
)

SMOKE = ModelConfig(
    name="deepseek-v2-lite-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    d_ff=128,
    vocab_size=512,
    attention=AttentionConfig(
        kind="mla", num_heads=4, num_kv_heads=4, head_dim=16,
        q_lora_rank=0, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=10_000.0, rope_scaling=YARN),
    moe=MoEConfig(num_experts=8, num_experts_per_tok=3,
                  num_shared_experts=2, d_ff_expert=16, d_ff_shared=32,
                  first_k_dense=1, d_ff_dense=128, capacity_factor=0.0,
                  norm_topk_prob=False,
                  aux_loss_coef=0.001),
    act="swiglu",
    norm_eps=1e-6,
)


@register_arch("deepseek-v2-lite")
def spec() -> ArchSpec:
    return ArchSpec(
        arch_id="deepseek-v2-lite",
        model=FULL,
        smoke=SMOKE,
        shapes=("train_4k", "prefill_32k", "decode_32k"),
        skip_shapes=("long_500k",),
        skip_reason="MLA compresses the cache but attention is still full "
                    "(quadratic); long_500k skipped per assignment rule",
        source="hf:deepseek-ai/DeepSeek-V2-Lite",
    )
