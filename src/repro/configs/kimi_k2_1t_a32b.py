from repro.configs.base import (MLAConfig, ModelConfig, MoEConfig, YaRNConfig,
                                register)

# moonshotai/Kimi-K2-Instruct config.json: 61 layers, the first dense; MLA
# with 64 heads; 384 routed experts (sigmoid scores, a selection-only
# correction bias, top-8 renormalised and scaled by 2.827) and one
# ungated shared expert; YaRN RoPE; 163840-row vocabulary, untied.
register(ModelConfig(
    name="kimi-k2-1t-a32b", arch_type="moe",
    num_layers=61, d_model=7168, num_heads=64, num_kv_heads=64,
    d_ff=18432, vocab_size=163840,
    block_pattern=("mla_moe",),
    rope_theta=50000.0,
    rope_scaling=YaRNConfig(factor=32.0,
                            original_max_position_embeddings=4096,
                            beta_fast=1.0, beta_slow=1.0, mscale=1.0,
                            mscale_all_dim=1.0),
    activation="silu", mlp_gated=True,
    mla=MLAConfig(q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=384, top_k=8, d_ff_expert=2048,
                  num_shared_experts=1, shared_d_ff=2048,
                  first_k_dense=1, dispatch="dropless",
                  scoring_func="sigmoid", correction_bias=True,
                  routed_scaling_factor=2.827,
                  shared_gate=False),
    norm_eps=1e-6, tie_embeddings=False,
    optimizer="adafactor", grad_accum=8,
    source="https://huggingface.co/moonshotai/Kimi-K2-Instruct/blob/main/"
           "config.json",
))
