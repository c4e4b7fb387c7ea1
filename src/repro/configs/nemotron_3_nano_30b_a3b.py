"""nemotron-3-nano-30b-a3b [pattern] — Mamba-2 / MoE / attention blocks.

52 blocks after hybrid_override_pattern (23 Mamba-2, 23 MoE, 6 GQA
attention), d_model=2688, vocab=131072.  Mamba-2: 64 heads x 64, 8 B/C
groups of state 128.  MoE: 128 ReLU^2 experts of width 1856, top-6 of
sigmoid scores times 2.5, one shared expert of width 3712.  Attention:
32 query heads, 2 KV heads of 128, no positional embedding.
[hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16]
"""
from repro.configs.base import ModelConfig, MoEConfig, SSMConfig

PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron-3-nano-30b-a3b",
    arch_type="pattern",
    layer_pattern=PATTERN,
    num_layers=len(PATTERN),
    d_model=2688,
    num_heads=32,
    num_kv_heads=2,
    head_dim=128,
    d_ff=1856,                 # routed expert width
    vocab_size=131072,
    rope_theta=0.0,            # attention without positional embedding
    norm="rmsnorm",
    norm_eps=1e-5,
    mlp="relu2",
    ssm=SSMConfig(state_dim=128, head_dim=64, num_heads=64, n_groups=8,
                  chunk_size=128, conv_width=4),
    moe=MoEConfig(num_experts=128, top_k=6, router="sigmoid",
                  routed_scale=2.5, shared_expert_ff=3712,
                  router_aux_weight=0.0),
    source="hf:nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16",
)
