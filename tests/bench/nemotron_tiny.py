"""The Nemotron-H training cell at the reduced preset's sizes, for
driving it on the CPU."""

from __future__ import annotations

import copy

from bench_tiny import SEED  # noqa: F401  (puts src/ and the root on the path)
from bench.run import cell_for

CELL = "nemotron3-nano-30b-a3b-journal.train8k"
# configuration keys at the sizes of src/repro/configs (REDUCED)
TINY = {"num_hidden_layers": 7, "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
        "intermediate_size": 48, "vocab_size": 256,
        "n_routed_experts_published": 16, "n_routed_experts": 4,
        "num_experts_per_tok": 3, "moe_intermediate_size": 48,
        "moe_shared_expert_intermediate_size": 96, "ssm_state_size": 16,
        "mamba_head_dim": 8, "mamba_num_heads": 8, "chunk_size": 16,
        "n_groups": 2, "compute_dtype": "float32"}


def tiny_model() -> dict:
    c = copy.deepcopy(cell_for(CELL, SEED).config)
    c.update(TINY)
    return c


def nemotron_cell(seed: int = SEED):
    c = cell_for(CELL, seed)
    c.config = copy.deepcopy(c.config)
    c.workload = copy.deepcopy(c.workload)
    c.config.update(TINY)
    c.params.update(batch=2, seq=64, ckpt_every=4, step_s=0.05,
                    reduced=True)
    return c
