"""DeepSeek-V2-Lite [arXiv:2405.04434] — multi-head latent attention in
front of DeepSeekMoE: 27 layers, the first with a dense FFN of 10944, the
other 26 with 64 routed experts (top-6) + 2 shared; 16 heads whose keys and
values come from a 512-wide latent row plus a 64-wide shared rope key a
token; YaRN positions (factor 40 over a 4096-token original context).
Published config: huggingface.co/deepseek-ai/DeepSeek-V2-Lite config.json."""
from .base import ModelConfig, register


@register("deepseek-v2-lite")
def deepseek_v2_lite() -> ModelConfig:
    return ModelConfig(
        name="deepseek-v2-lite",
        family="moe",
        source="arXiv:2405.04434",
        num_layers=27,
        d_model=2048,
        vocab_size=102400,
        num_heads=16,
        num_kv_heads=16,
        d_ff=10944,
        ffn_type="moe",
        first_k_dense_replace=1,
        n_routed_experts=64,
        n_shared_experts=2,
        top_k=6,
        moe_d_ff=1408,
        shared_d_ff=1408,
        activation="silu",
        kv_lora_rank=512,
        qk_nope_head_dim=128,
        qk_rope_head_dim=64,
        v_head_dim=128,
        rope_theta=10000.0,
        yarn_factor=40.0,
        yarn_original_max_position=4096,
        yarn_beta_fast=32.0,
        yarn_beta_slow=1.0,
        yarn_mscale=0.707,
        yarn_mscale_all_dim=0.707,
        tie_embeddings=False,
    )
