"""jamba2-mini — AI21 Jamba 1.5/1.6/1.7 Mini family: Mamba-1 and attention
layers 7:1, routed experts on every other layer.

[hf:ai21labs/AI21-Jamba2-Mini config.json]  32L d_model=4096 32H (GQA kv=8,
head_dim 128, no positional encoding) d_ff=14336 vocab=65536; Mamba-1
d_state=16 conv=4 (with bias) expand=2 dt_rank=256, dt/B/C RMS-normalised;
16 experts of width 14336, top-2 without renormalisation, on layers
i % 2 == 1; attention on layers i % 8 == 4.
"""
from repro.configs.base import ATTN, SSM, ModelConfig, register

CONFIG = register(ModelConfig(
    name="jamba2-mini",
    family="hybrid",
    num_layers=32,
    d_model=4096,
    num_heads=32,
    num_kv_heads=8,
    head_dim=128,
    d_ff=14_336,
    vocab_size=65_536,
    num_experts=16,
    experts_per_token=2,
    moe_d_ff=14_336,
    moe_layer_period=2,
    moe_layer_offset=1,
    moe_dropless=True,
    moe_renormalize=False,
    ssm_state=16,
    ssm_conv=4,
    ssm_expand=2,
    dt_rank=256,
    ssm_conv_bias=True,
    ssm_dt_norm=True,
    layer_pattern=(SSM, SSM, SSM, SSM, ATTN, SSM, SSM, SSM),
    use_rope=False,
    norm_eps=1e-6,
    source="hf:ai21labs/AI21-Jamba2-Mini (BONUS, unassigned)",
))
