"""DeepSeek-V3 [hf:deepseek-ai/DeepSeek-V3, config.json]: 61 layers of
multi-head latent attention (MLA: q_lora_rank 1536, kv_lora_rank 512, head
dims 128 nope + 64 rope, v 128); the first 3 layers dense (d_ff 18432), the
other 58 MoE: 256 routed experts of width 2048, top-8 under sigmoid scores
from the best 4 of 8 groups, normalised and scaled by 2.5, plus 1 shared
expert.

The one multi-token-prediction module (``num_nextn_predict_layers``) is
left out: a decode step here yields one token per sequence.

The model substrate (``repro.models``) builds no MLA layer, so this config
serves the DVFS step path (``repro.dvfs_runtime.telemetry.step_ops``) and
is not in ``ARCH_IDS``. ``SMOKE`` is its small same-shape form (``reduced``
knows no MLA field)."""
from repro.configs.base import ModelConfig, MoEConfig

CONFIG = ModelConfig(
    name="deepseek-v3", family="moe",
    n_layers=61, d_model=7168, n_heads=128, n_kv_heads=128,
    d_ff=18432, vocab=129280,
    rope_theta=10_000.0, norm_eps=1e-6, attn_kind="full",
    q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    first_k_dense_replace=3,
    moe=MoEConfig(num_experts=256, top_k=8, num_shared=1,
                  expert_d_ff=2048, shared_d_ff=2048,
                  routed_scaling_factor=2.5, n_group=8, topk_group=4),
)

SMOKE = ModelConfig(
    name="deepseek-v3-smoke", family="moe",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=96, vocab=512,
    rope_theta=10_000.0, norm_eps=1e-6, attn_kind="full",
    q_lora_rank=32, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
    first_k_dense_replace=1,
    moe=MoEConfig(num_experts=16, top_k=4, num_shared=1,
                  expert_d_ff=24, shared_d_ff=24,
                  routed_scaling_factor=2.5, n_group=4, topk_group=2),
)
