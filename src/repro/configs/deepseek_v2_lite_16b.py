"""deepseek-v2-lite-16b [moe] — MLA (kv_lora=512, no q compression, YaRN),
2 shared + 64 routed experts top-6 with unnormalised softmax gates,
dense first layer [hf:deepseek-ai/DeepSeek-V2-Lite, arXiv:2405.04434]."""
from repro.models.config import ModelConfig, MoEConfig, MLAConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b", arch_type="moe",
    num_layers=27, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, head_dim=128,
    block_pattern=("mla",), prefix_pattern=("mla",),  # layer 0 dense
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                  rope_factor=40.0, original_max_position=4096,
                  beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                  mscale_all_dim=0.707),
    # router_aux_coef: the expert-level balance factor the DeepSeek-V2
    # paper gives for V2-Lite (0.001); the published config carries none
    moe=MoEConfig(num_experts=64, top_k=6, d_expert=1408,
                  num_shared_experts=2, shared_d_expert=1408, first_dense=1,
                  router_aux_coef=0.001, normalize_gates=False,
                  routed_scaling_factor=1.0),
    rope_theta=10000.0, mlp_kind="swiglu", tie_embeddings=False,
    source="hf:deepseek-ai/DeepSeek-V2-Lite",
)


def smoke_config() -> ModelConfig:
    return CONFIG.replace(
        name="deepseek-v2-lite-smoke", num_layers=3, d_model=128,
        num_heads=4, num_kv_heads=4, head_dim=32, d_ff=256, vocab_size=512,
        mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0, qk_nope_head_dim=16,
                      qk_rope_head_dim=8, v_head_dim=16, rope_factor=40.0,
                      original_max_position=64, beta_fast=32.0,
                      beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
        moe=MoEConfig(num_experts=8, top_k=3, d_expert=64,
                      num_shared_experts=2, shared_d_expert=32, first_dense=1,
                      router_aux_coef=0.001, normalize_gates=False,
                      held_experts=4))
