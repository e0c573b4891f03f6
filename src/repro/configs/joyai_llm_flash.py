"""joyai-llm-flash [hf:jdopensource/JoyAI-LLM-Flash; the DeepSeek-V3 block,
arXiv:2412.19437].

40L d_model=2048 32H MLA with q-LoRA (q_lora 1536, kv_lora 512, qk 128+64,
v 128), RoPE theta 3.2e7 without scaling; first layer dense (d_ff 7168),
then MoE layers of 1 shared + 256 routed experts of 768, top 8 by sigmoid
affinity plus a correction bias (noaux_tc, one group), gates renormalised
and scaled by 2.5; one multi-token-prediction depth; untied head over
129280 tokens.  Not in the source's config.json, taken from DeepSeek-V3's
published training hyper-parameters: the bias update rate 0.001, the
balance loss's weight 1e-4 and the depth's loss weight 0.3.  Training is
dropless (``MoEConfig.capacity_factor`` 0).
"""
from repro.core.config import (ArchSpec, AttentionConfig, MoEConfig,
                               ModelConfig, register_arch)

FULL = ModelConfig(
    name="joyai-llm-flash",
    family="moe",
    num_layers=40,
    d_model=2048,
    d_ff=7168,
    vocab_size=129_280,
    attention=AttentionConfig(
        kind="mla", num_heads=32, num_kv_heads=32, head_dim=128,
        q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        rope_theta=32_000_000.0),
    moe=MoEConfig(num_experts=256, num_experts_per_tok=8,
                  num_shared_experts=1, d_ff_expert=768, d_ff_shared=768,
                  first_k_dense=1, d_ff_dense=7168, capacity_factor=0.0,
                  norm_topk_prob=True, scoring="sigmoid",
                  routed_scaling_factor=2.5, bias_update_rate=0.001,
                  aux_loss_coef=1e-4, grouped_rows_floor=4.0),
    mtp_depth=1,
    mtp_loss_coef=0.3,
    act="swiglu",
    norm_eps=1e-6,
    max_seq_len=131_072,
)

SMOKE = ModelConfig(
    name="joyai-llm-flash-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    d_ff=128,
    vocab_size=512,
    attention=AttentionConfig(
        kind="mla", num_heads=4, num_kv_heads=4, head_dim=16,
        q_lora_rank=48, kv_lora_rank=32,
        qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
        rope_theta=32_000_000.0),
    moe=MoEConfig(num_experts=32, num_experts_per_tok=4,
                  num_shared_experts=1, d_ff_expert=16, d_ff_shared=16,
                  first_k_dense=1, d_ff_dense=128, capacity_factor=0.0,
                  norm_topk_prob=True, scoring="sigmoid",
                  routed_scaling_factor=2.5, bias_update_rate=0.001,
                  aux_loss_coef=1e-4),
    mtp_depth=1,
    mtp_loss_coef=0.3,
    act="swiglu",
    norm_eps=1e-6,
)


@register_arch("joyai-llm-flash")
def spec() -> ArchSpec:
    return ArchSpec(
        arch_id="joyai-llm-flash",
        model=FULL,
        smoke=SMOKE,
        shapes=("train_4k", "prefill_32k", "decode_32k"),
        skip_shapes=("long_500k",),
        skip_reason="MLA compresses the cache but attention is still full "
                    "(quadratic); long_500k skipped per assignment rule",
        source="hf:jdopensource/JoyAI-LLM-Flash",
    )
