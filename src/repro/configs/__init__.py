"""Architecture registry: the ten assigned configs, reduced smoke-test
variants, and ShapeDtypeStruct input specs for every (arch × shape) cell.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models.config import (InputShape, ModelConfig, SHAPES,
                             applicable_shapes)
from . import (command_r_35b, deepseek_v3_671b, gemma2_9b, hubert_xlarge,
               jamba_1_5_large_398b, llava_next_34b, mamba2_130m,
               moonshot_v1_16b_a3b, nemotron3_nano_30b_a3b, qwen2_7b,
               starcoder2_3b)

_MODULES = [hubert_xlarge, moonshot_v1_16b_a3b, deepseek_v3_671b,
            mamba2_130m, jamba_1_5_large_398b, starcoder2_3b, gemma2_9b,
            command_r_35b, qwen2_7b, llava_next_34b, nemotron3_nano_30b_a3b]

ARCHS: Dict[str, ModelConfig] = {m.CONFIG.name: m.CONFIG for m in _MODULES}
ARCH_NAMES = list(ARCHS)
# presets with a reduced variant of their own (under its own name)
_REDUCED: Dict[str, ModelConfig] = {m.CONFIG.name: m.REDUCED
                                    for m in _MODULES if hasattr(m, "REDUCED")}


def get_config(name: str) -> ModelConfig:
    if name not in ARCHS:
        raise KeyError(f"unknown arch {name!r}; have {ARCH_NAMES}")
    return ARCHS[name]


def reduced_config(name: str) -> ModelConfig:
    """Same family/features, smoke-test scale (CPU-runnable)."""
    cfg = get_config(name)
    if name in _REDUCED:
        return _REDUCED[name]
    kw: Dict[str, Any] = dict(
        d_model=128,
        n_heads=4,
        n_kv_heads=min(cfg.n_kv_heads, 4) if cfg.n_kv_heads >= 4
        else cfg.n_kv_heads,
        head_dim=32,
        d_ff=0 if cfg.d_ff == 0 else 256,
        vocab_size=512,
        first_dense_layers=min(cfg.first_dense_layers, 1),
        param_dtype="float32",
        compute_dtype="float32",
    )
    kw["n_layers"] = kw["first_dense_layers"] + cfg.block_period
    if cfg.use_mla:
        kw.update(q_lora_rank=64, kv_lora_rank=32, qk_nope_dim=32,
                  qk_rope_dim=16, v_head_dim=32)
    if cfg.n_experts:
        # capacity_factor = E makes dispatch provably dropless, so smoke
        # tests are exactly causal (capacity drops depend on batch length)
        kw.update(n_experts=8,
                  experts_per_token=min(cfg.experts_per_token, 3),
                  moe_d_ff=128, capacity_factor=8.0)
    if cfg.family in ("ssm", "hybrid"):
        kw.update(ssm_state_dim=32, ssm_head_dim=16, ssm_chunk=32,
                  ssm_n_groups=min(cfg.ssm_n_groups, 2))
    if cfg.sliding_window:
        kw.update(sliding_window=64)
    if cfg.input_kind != "tokens":
        kw.update(frontend_dim=64)
    if cfg.n_patches:
        kw.update(n_patches=16)
    return replace(cfg, **kw)


# ---------------------------------------------------------------------- #
# input specs per (arch × shape)
# ---------------------------------------------------------------------- #

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: InputShape | str,
                per_pod_batch: Optional[int] = None) -> Dict[str, Any]:
    """ShapeDtypeStruct stand-ins for one (arch × shape) cell.

    Returns {"batch": {...}, "cache": ... | None, "index": ... | None,
    "kind": "train"|"serve"}.  ``per_pod_batch`` overrides the global
    batch (multi-pod runs split the global batch across pods only for
    data; the dry-run keeps the global batch and shards it).
    """
    if isinstance(shape, str):
        shape = SHAPES[shape]
    B = per_pod_batch or shape.global_batch
    S = shape.seq_len
    emb_dt = cfg.compute_dtype

    def token_batch(seq, with_labels):
        b: Dict[str, Any] = {}
        if cfg.input_kind == "frames":
            b["frames"] = _sds((B, seq, cfg.frontend_dim), emb_dt)
        elif cfg.input_kind == "tokens+patches":
            npatch = min(cfg.n_patches, max(seq - 1, 0)) if seq > 1 else 0
            if npatch and seq > npatch:
                b["patches"] = _sds((B, npatch, cfg.frontend_dim), emb_dt)
                b["tokens"] = _sds((B, seq - npatch), jnp.int32)
            else:
                b["tokens"] = _sds((B, seq), jnp.int32)
        else:
            b["tokens"] = _sds((B, seq), jnp.int32)
        if with_labels:
            b["labels"] = _sds((B, seq), jnp.int32)
        return b

    from ..models import model as M
    if shape.kind == "train":
        return {"kind": "train", "batch": token_batch(S, True),
                "cache": None, "index": None}
    if shape.kind == "prefill":
        cache = None
        if cfg.causal:
            cache = M.cache_specs(cfg, B, S)
        return {"kind": "serve", "batch": token_batch(S, False),
                "cache": cache, "index": _sds((), jnp.int32)
                if cache is not None else None}
    # decode: one new token against a seq_len-deep cache
    cache = M.cache_specs(cfg, B, S)
    batch = {"tokens": _sds((B, 1), jnp.int32)}
    return {"kind": "serve", "batch": batch, "cache": cache,
            "index": _sds((), jnp.int32)}


__all__ = ["ARCHS", "ARCH_NAMES", "get_config", "reduced_config",
           "input_specs", "SHAPES", "applicable_shapes"]
