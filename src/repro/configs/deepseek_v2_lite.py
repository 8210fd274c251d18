"""deepseek-v2-lite [moe]: 27L d_model=2048 16H MLA (no query compression)
d_ff_expert=1408 vocab=102400, MoE 64e top-6 (gates not renormalised),
2 shared experts; MLA kv_lora=512; YaRN rotary (factor 40 over 4096).
[hf:deepseek-ai/DeepSeek-V2-Lite config.json]

Layer 0 is a dense FFN (d_ff 10944); the remaining 26 layers are MoE.
long_500k: SKIPPED - full (MLA) attention, quadratic at 500k (DESIGN.md).
"""
from repro.models.config import ArchConfig, MLAConfig
from repro.models.layers import YarnConfig
from repro.models.moe import MoEConfig

CONFIG = ArchConfig(
    name="deepseek-v2-lite",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=10944, vocab=102400,
    head=("global_dense",), pattern=("global",),
    mla=MLAConfig(q_lora=None, kv_lora=512, qk_nope=128, qk_rope=64, v_head=128),
    moe=MoEConfig(n_experts=64, top_k=6, d_ff_expert=1408, n_shared=2,
                  d_ff_dense=2816, router_scale=1.0, norm_topk=False),
    rope_theta=10_000.0,
    yarn=YarnConfig(factor=40.0, original_max=4096, beta_fast=32.0,
                    beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
)
