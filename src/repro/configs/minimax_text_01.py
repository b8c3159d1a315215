"""minimax-text-01 [hybrid]: 80L d_model=6144 64H (GQA kv=8) head_dim=128,
MoE 32 experts top-2 (expert width 9216, no shared expert), vocab=200064.
[huggingface.co/MiniMaxAI/MiniMax-Text-01, config.json] 80 = 10 periods of
7 lightning (linear-attention) layers and 1 softmax GQA layer, each layer
post-norm (alpha = (2*80)^(1/4), beta = 1) with a Mixtral-style MoE.

A lightning layer is the paper's sequence topological mask with g = exp,
degree 1 and fixed per-head coefficients [0, -s_{h,l}], unnormalised, on
SiLU features (models/attention.py `lightning_*`)."""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="minimax-text-01",
    family="hybrid",
    num_layers=80,
    d_model=6144,
    num_heads=64, num_kv_heads=8, head_dim=128,
    d_ff=9216,
    vocab_size=200064,
    superblock=("lightning",) * 7 + ("softmax",),
    num_superblocks=10,
    lightning_decay_layers=80,
    topo_g="exp", topo_degree=1, topo_synced=False, topo_attn_impl="pallas",
    attn_impl="chunked",
    rope_theta=1e7,
    rotary_dim=64,
    moe=True,
    num_experts=32,
    top_k=2,
    moe_d_ff=9216,
    moe_held=tuple(range(32)),
    norm_eps=1e-5,
    postnorm=True,
    residual_alpha=3.5565588200778455,
    residual_beta=1.0,
)

SMOKE_CONFIG = CONFIG.replace(
    num_layers=8, num_superblocks=1, d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, rotary_dim=8, d_ff=32, moe_d_ff=32, vocab_size=512,
    moe_held=tuple(range(8)))
