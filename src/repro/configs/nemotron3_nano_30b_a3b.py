"""NVIDIA-Nemotron-3-Nano-30B-A3B — hybrid Mamba-2 / sparse experts /
GQA, one mixer per residual layer [hf: nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16, config.json].

Published: 52 layers in the pattern ``PUBLISHED_PATTERN`` (M Mamba-2,
E experts, * attention), d_model 2688, vocab 131072, untied embeddings,
eps 1e-5.  Mamba-2: 64 heads of 64 (inner width 4096), 8 B/C groups,
state 128, conv 4 (with bias), chunk 128; gate, then RMSNorm per group.
Experts: 128 routed, top 6 by sigmoid score, top-6 gates renormalised
and scaled by 2.5; each a non-gated relu^2 MLP of width 1856, plus one
shared relu^2 expert of width 3712.  Attention: 32 query heads and 2 KV
heads of 128, no bias.

``CONFIG`` is one chip's share of one pipeline stage of it: the stage is
one period of the pattern, ``MEMEM*E`` (3 Mamba-2, 3 expert and 1
attention layer), for an expert-parallel group of 16 chips per layer,
so the chip holds 8 of the 128 experts (``experts_held``; the router
still scores all 128) and an eighth of the vocabulary (16384 rows).
Every width is the published one."""

from ..models.config import ModelConfig

PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"

CONFIG = ModelConfig(
    name="nemotron3-nano-30b-a3b",
    family="hybrid",
    n_layers=7,
    d_model=2688,
    n_heads=32,
    n_kv_heads=2,
    head_dim=128,
    d_ff=1856,
    vocab_size=16384,
    layer_pattern="MEMEM*E",
    use_rope=False,
    attn_chunk_q=128,
    gated_mlp=False,
    mlp_act="relu2",
    n_experts=128,
    experts_held=8,
    expert_offset=0,
    n_shared_experts=1,
    experts_per_token=6,
    moe_d_ff=1856,
    moe_shared_d_ff=3712,
    router_score="sigmoid",
    routed_scaling=2.5,
    router_aux_weight=0.0,
    ssm_state_dim=128,
    ssm_head_dim=64,
    ssm_n_heads=64,
    ssm_conv_width=4,
    ssm_chunk=128,
    ssm_n_groups=8,
    ssm_gate_first=True,
    tie_embeddings=False,
    norm_eps=1e-5,
    param_dtype="float32",
    compute_dtype="bfloat16",
    remat="layer",
)

# the same stage at a CPU-test size: every mechanism, small widths
REDUCED = ModelConfig(
    name="nemotron3-nano-30b-a3b-reduced",
    family="hybrid",
    n_layers=7,
    d_model=64,
    n_heads=4,
    n_kv_heads=2,
    head_dim=16,
    d_ff=48,
    vocab_size=256,
    layer_pattern="MEMEM*E",
    use_rope=False,
    attn_chunk_q=16,
    gated_mlp=False,
    mlp_act="relu2",
    n_experts=16,
    experts_held=4,
    expert_offset=0,
    n_shared_experts=1,
    experts_per_token=3,
    moe_d_ff=48,
    moe_shared_d_ff=96,
    router_score="sigmoid",
    routed_scaling=2.5,
    router_aux_weight=0.0,
    ssm_state_dim=16,
    ssm_head_dim=8,
    ssm_n_heads=8,
    ssm_conv_width=4,
    ssm_chunk=16,
    ssm_n_groups=2,
    ssm_gate_first=True,
    tie_embeddings=False,
    norm_eps=1e-5,
    param_dtype="float32",
    compute_dtype="float32",
    remat="layer",
)
