"""DeepSeek-V2-Lite 16B: MLA attention (kv_lora=512), first layer dense MLP,
remaining layers MoE with 64 routed experts top-6 + 2 shared.
[arXiv:2405.04434; hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Routing as published: softmax gates, the top-6 not renormalised
(``norm_topk_prob: false``, ``routed_scaling_factor`` 1).  Rope as
published: YaRN with factor 40 over 4096 original positions, which also
scales the attention softmax by mscale(0.707)^2.
"""
from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,            # MLA: KV heads == heads after latent expansion
    head_dim=128,
    d_ff=10944,               # dense-MLP hidden for layer 0 (per model card)
    vocab_size=102400,
    segments=((("mla",), 1), (("mla_moe",), 26)),
    activation="swiglu",
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=None,
                  nope_head_dim=128, rope_head_dim=64, v_head_dim=128,
                  yarn=YaRNConfig(factor=40.0, original_max_position=4096,
                                  beta_fast=32.0, beta_slow=1.0,
                                  mscale=0.707, mscale_all_dim=0.707)),
    moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_ff=1408,
                  norm_topk=False, routed_scale=1.0),
    source="arXiv:2405.04434",
)
