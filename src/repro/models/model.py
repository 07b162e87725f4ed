"""Composable model stack for all ten architectures.

The stack scans over identical *blocks* (one repetition of the layer
pattern — see config.block_pattern), so a 72-layer hybrid lowers as a
9-step scan over an 8-layer block: the HLO stays small enough to compile
for 512 devices, and ``jax.checkpoint`` on the block gives layer-granular
remat.

Public entry points (all functional, params are plain pytrees):

  param_specs(cfg)                 — ShapeDtypeStruct tree (no allocation)
  init_params(rng, cfg)            — smoke-test-scale initialization
  forward_train(params, cfg, batch)- (loss, metrics)
  serve_step(params, cfg, inputs, cache, index) — prefill & decode
  cache_specs(cfg, batch, max_len) — serving-state ShapeDtypeStructs
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .. import obs
from . import layers as L
from .config import InputShape, LayerKind, ModelConfig

Params = Dict[str, Any]

# Activation-sharding constraint for the residual stream [B, S, D].
# Set by the launcher (see distributed.sharding.activation_spec) so model
# code stays mesh-agnostic; None = let XLA propagate.
_ACT_SPEC: Optional[Any] = None


def set_activation_spec(spec) -> None:
    """spec: jax PartitionSpec for [batch, seq, d_model] activations,
    or None to disable.  Applied to the residual stream at the embed
    boundary and at every scanned-block boundary — keeps SPMD from
    dropping the batch sharding in the backward pass."""
    global _ACT_SPEC
    _ACT_SPEC = spec


def _constrain(h):
    if _ACT_SPEC is None:
        return h
    return jax.lax.with_sharding_constraint(h, _ACT_SPEC)


# ---------------------------------------------------------------------- #
# parameter specs
# ---------------------------------------------------------------------- #

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def _spec_tree(shapes, cfg: ModelConfig, fp32_keys=("norm", "a_log",
                                                    "dt_bias", "d_skip")):
    """shape-dict -> ShapeDtypeStruct tree; norms/SSM scalars kept fp32."""
    def conv(path, shape):
        name = path.lower()
        dt = jnp.float32 if any(k in name for k in fp32_keys) \
            else cfg.param_dtype
        return _sds(shape, dt)
    out = {}
    def rec(prefix, node, dst):
        for k, v in node.items():
            if isinstance(v, dict):
                dst[k] = {}
                rec(prefix + "/" + k, v, dst[k])
            else:
                dst[k] = conv(prefix + "/" + k, v)
    rec("", shapes, out)
    return out


def _layer_shapes(cfg: ModelConfig, kind: LayerKind) -> Dict[str, Any]:
    D = cfg.d_model
    s: Dict[str, Any] = {"ln1": {"w": (D,)}}
    if kind.mixer == "moe":                    # single-mixer layer
        s["ffn"] = L.moe_params_shapes(cfg)
        return s
    if kind.mixer == "attn":
        s["attn"] = L.mla_params_shapes(cfg) if cfg.use_mla \
            else L.gqa_params_shapes(cfg)
    else:
        s["ssm"] = L.ssm_params_shapes(cfg)
    has_ffn = (kind.moe or cfg.d_ff > 0) and not cfg.layer_pattern
    if not has_ffn:                      # mamba2, nemotron-h: mixer only
        return s
    if not cfg.parallel_block:
        s["ln2"] = {"w": (D,)}
    s["ffn"] = L.moe_params_shapes(cfg) if kind.moe \
        else L.mlp_params_shapes(cfg, cfg.d_ff)
    if cfg.use_post_norm:
        s["post_ln1"] = {"w": (D,)}
        s["post_ln2"] = {"w": (D,)}
    return s


def _block_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    return {f"l{i}": _layer_shapes(cfg, kind)
            for i, kind in enumerate(cfg.block_pattern())}


def param_specs(cfg: ModelConfig) -> Params:
    D, V = cfg.d_model, cfg.vocab_size
    shapes: Dict[str, Any] = {}
    if cfg.input_kind in ("tokens", "tokens+patches"):
        shapes["embed"] = {"w": (V, D)}
    if cfg.input_kind == "frames":
        shapes["frame_proj"] = {"w": (cfg.frontend_dim, D), "b": (D,)}
    if cfg.input_kind == "tokens+patches":
        shapes["patch_proj"] = {"w": (cfg.frontend_dim, D), "b": (D,)}
    dense_kind = LayerKind(mixer="attn", moe=False, local=False)
    for i in range(cfg.first_dense_layers):
        shapes[f"dense{i}"] = _layer_shapes(cfg, dense_kind)
    shapes["blocks"] = _block_shapes(cfg)
    shapes["final_norm"] = {"w": (D,)}
    if not cfg.tie_embeddings or cfg.input_kind == "frames":
        shapes["lm_head"] = {"w": (D, V)}
    if cfg.mtp_depth:
        shapes["mtp"] = {"proj": {"w": (2 * D, D)},
                         "block": _layer_shapes(cfg, dense_kind),
                         "norm": {"w": (D,)}}
    specs = _spec_tree(shapes, cfg)
    # stack the scanned block along a leading n_blocks axis
    nb = cfg.n_blocks
    specs["blocks"] = jax.tree_util.tree_map(
        lambda s: _sds((nb, *s.shape), s.dtype), specs["blocks"])
    return specs


def init_params(rng: jax.Array, cfg: ModelConfig) -> Params:
    """Initialize real arrays matching param_specs (smoke-test scale)."""
    specs = param_specs(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(specs)
    keys = jax.random.split(rng, len(flat))
    leaves = []
    for (path, spec), key in zip(flat, keys):
        name = jax.tree_util.keystr(path).lower()
        shape, dtype = spec.shape, spec.dtype
        if "a_log" in name:
            leaf = jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        elif "dt_bias" in name:
            u = jax.random.uniform(key, shape, jnp.float32, 1e-3, 1e-1)
            leaf = u + jnp.log(-jnp.expm1(-u))          # softplus^-1
        elif "d_skip" in name:
            leaf = jnp.ones(shape, jnp.float32)
        elif name.endswith("['b']") or "ln" in name or "norm" in name:
            leaf = jnp.zeros(shape, dtype)
        else:
            fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
            leaf = (jax.random.normal(key, shape, jnp.float32) *
                    (0.02 if fan_in <= 0 else min(0.02, fan_in ** -0.5))
                    ).astype(dtype)
        leaves.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ---------------------------------------------------------------------- #
# forward machinery
# ---------------------------------------------------------------------- #

def _cast_compute(p, cfg: ModelConfig):
    """Mixed-precision policy: matmul weights (ndim>=2, floating) compute
    in compute_dtype regardless of storage dtype; 1-D leaves (norm gains,
    A_log, dt_bias, biases) keep their own (fp32) semantics."""
    dt = jnp.dtype(cfg.compute_dtype)

    def conv(a):
        if hasattr(a, "dtype") and a.ndim >= 2 and \
                jnp.issubdtype(a.dtype, jnp.floating) and a.dtype != dt:
            return a.astype(dt)
        return a
    return jax.tree_util.tree_map(conv, p)


def _zero_aux(cfg: ModelConfig) -> Dict[str, jax.Array]:
    """What the layers add up besides the residual stream: the router's
    auxiliary loss and, where experts are held, the rows the held
    experts computed and the largest held expert's rows."""
    aux = {"aux": jnp.zeros((), jnp.float32)}
    if cfg.experts_held:
        aux[obs.MOE_ROWS] = jnp.zeros((), jnp.int32)
        aux[obs.MOE_MAX_ROWS] = jnp.zeros((), jnp.int32)
    return aux


def _add_aux(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def _moe_aux(cfg: ModelConfig, aux, load):
    out = _zero_aux(cfg)
    out["aux"] = aux
    if cfg.experts_held:
        out[obs.MOE_ROWS] = load.sum()
        out[obs.MOE_MAX_ROWS] = load.max()
    return out


def _apply_layer(h, p, cfg: ModelConfig, kind: LayerKind, cache, index):
    """One residual layer.  Returns (h, new_cache, aux)."""
    raw = p
    p = _cast_compute(p, cfg)
    aux = _zero_aux(cfg)
    u = L.rms_norm(h, p["ln1"]["w"], cfg.norm_eps)
    if kind.mixer == "moe":                    # single-mixer expert layer
        ffn = p["ffn"]
        if cfg.router_score == "sigmoid":      # router kept in float32
            ffn = dict(ffn, router=raw["ffn"]["router"])
        moe = L.moe_held_ffn if cfg.experts_held else L.moe_ffn
        ff, a, load = moe(u, ffn, cfg)
        return h + ff, cache, _moe_aux(cfg, a, load)
    if kind.mixer == "attn":
        mix, new_cache = (L.mla_attention if cfg.use_mla else
                          partial(L.gqa_attention, local=kind.local))(
            u, p["attn"], cfg, cache=cache, index=index)
    else:
        mix, new_cache = L.ssm_mixer(u, p["ssm"], cfg, cache=cache)
    if "ffn" not in p:                         # mamba2: mixer-only layer
        return h + mix, new_cache, aux
    if cfg.parallel_block:                     # command-r: shared-norm ||
        ff = L.mlp(u, p["ffn"], cfg)
        return h + mix + ff, new_cache, aux
    if cfg.use_post_norm:
        mix = L.rms_norm(mix, p["post_ln1"]["w"], cfg.norm_eps)
    h = h + mix
    u2 = L.rms_norm(h, p["ln2"]["w"], cfg.norm_eps)
    if kind.moe:
        ff, a, load = L.moe_ffn(u2, p["ffn"], cfg)
        aux = _moe_aux(cfg, a, load)
    else:
        ff = L.mlp(u2, p["ffn"], cfg)
    if cfg.use_post_norm:
        ff = L.rms_norm(ff, p["post_ln2"]["w"], cfg.norm_eps)
    return h + ff, new_cache, aux


def _layer_cache_spec(cfg: ModelConfig, kind: LayerKind, batch: int,
                      max_len: int):
    if kind.mixer == "ssm":
        return L.ssm_cache_spec(cfg, batch)
    if cfg.use_mla:
        return L.mla_cache_spec(cfg, batch, max_len)
    return L.gqa_cache_spec(cfg, batch, max_len)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """Serving state: stacked per-block caches + dense-layer caches."""
    pattern = cfg.block_pattern()
    block = {f"l{i}": _layer_cache_spec(cfg, kind, batch, max_len)
             for i, kind in enumerate(pattern)}
    nb = cfg.n_blocks
    stacked = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((nb, *s.shape), s.dtype), block)
    dense_kind = LayerKind(mixer="attn")
    out = {"blocks": stacked}
    for i in range(cfg.first_dense_layers):
        out[f"dense{i}"] = _layer_cache_spec(cfg, dense_kind, batch, max_len)
    return out


def init_cache(cfg: ModelConfig, batch: int, max_len: int):
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype),
                                  cache_specs(cfg, batch, max_len))


def apply_block(bp, h, cfg: ModelConfig, bc=None, index=None):
    """Apply one block (one repetition of the layer pattern).
    Returns (h, new_block_cache, aux)."""
    pattern = cfg.block_pattern()
    ncs = {}
    aux_acc = _zero_aux(cfg)
    for i, kind in enumerate(pattern):
        c = None if bc is None else bc[f"l{i}"]
        layer = partial(_apply_layer, cfg=cfg, kind=kind, index=index)
        if cfg.remat == "layer" and c is None:
            layer = jax.checkpoint(
                layer, policy=jax.checkpoint_policies.nothing_saveable)
        h, nc, aux = layer(h, bp[f"l{i}"], cache=c)
        aux_acc = _add_aux(aux_acc, aux)
        ncs[f"l{i}"] = nc if nc is not None else {}
    return h, ncs, aux_acc


def _run_stack(params: Params, cfg: ModelConfig, h, cache, index):
    """Dense prologue + scanned blocks.  Returns (h, new_cache, aux)."""
    aux_total = _zero_aux(cfg)
    new_cache: Dict[str, Any] = {}
    dense_kind = LayerKind(mixer="attn")
    for i in range(cfg.first_dense_layers):
        c = None if cache is None else cache[f"dense{i}"]
        h, nc, aux = _apply_layer(h, params[f"dense{i}"], cfg, dense_kind,
                                  c, index)
        aux_total = _add_aux(aux_total, aux)
        if nc is not None:
            new_cache[f"dense{i}"] = nc

    def block_body(carry, xs):
        hh, aux_acc = carry
        bp, bc = xs
        hh, ncs, aux = apply_block(bp, _constrain(hh), cfg, bc, index)
        return (_constrain(hh), _add_aux(aux_acc, aux)), ncs

    body = block_body
    if cfg.remat == "block":
        body = jax.checkpoint(block_body,
                              policy=jax.checkpoint_policies.nothing_saveable)
    bc = cache["blocks"] if cache is not None else None
    (h, aux_total), block_caches = lax.scan(
        body, (h, aux_total), (params["blocks"], bc),
        unroll=True if cfg.scan_unroll else 1)
    if cache is not None:
        new_cache["blocks"] = block_caches
    return h, (new_cache if cache is not None else None), aux_total


def _embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Any]):
    """Token/frame/patch inputs -> [B,S,D] activations (frontends are
    stubs per the brief: frames/patches arrive as precomputed embeddings)."""
    dt = jnp.dtype(cfg.compute_dtype)
    parts = []
    if cfg.input_kind == "frames":
        h = jnp.einsum("bsf,fd->bsd", batch["frames"].astype(dt),
                       params["frame_proj"]["w"].astype(dt))
        h = h + params["frame_proj"]["b"].astype(dt)
        return h
    if cfg.input_kind == "tokens+patches" and "patches" in batch:
        hp = jnp.einsum("bsf,fd->bsd", batch["patches"].astype(dt),
                        params["patch_proj"]["w"].astype(dt))
        hp = hp + params["patch_proj"]["b"].astype(dt)
        parts.append(hp)
    if "tokens" in batch:
        ht = params["embed"]["w"].astype(dt)[batch["tokens"]]
        if cfg.scale_embeddings:              # gemma-style embed scaling
            ht = ht * jnp.asarray(math.sqrt(cfg.d_model), dt)
        parts.append(ht)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _logits(params: Params, cfg: ModelConfig, h):
    h = L.rms_norm(h, params["final_norm"]["w"], cfg.norm_eps)
    if cfg.tie_embeddings and cfg.input_kind != "frames":
        w = params["embed"]["w"]
        logits = jnp.einsum("bsd,vd->bsv", h, w.astype(h.dtype))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h,
                            params["lm_head"]["w"].astype(h.dtype))
    return L.softcap(logits, cfg.final_logit_softcap)


def cross_entropy(logits, labels, ignore: int = -1):
    """fp32 CE with ignore mask; logits [B,S,V] (any float dtype)."""
    lf = logits.astype(jnp.float32)
    m = lf.max(axis=-1, keepdims=True)
    lse = jnp.log(jnp.sum(jnp.exp(lf - m), axis=-1)) + m[..., 0]
    safe = jnp.maximum(labels, 0)
    gold = jnp.take_along_axis(lf, safe[..., None], axis=-1)[..., 0]
    nll = lse - gold
    mask = (labels != ignore).astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(mask.sum(), 1.0)


def forward_train(params: Params, cfg: ModelConfig, batch: Dict[str, Any]
                  ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Training forward: returns (scalar loss fp32, metrics)."""
    h = _constrain(_embed_inputs(params, cfg, batch))
    h, _, aux = _run_stack(params, cfg, h, cache=None, index=None)
    logits = _logits(params, cfg, h)
    loss = cross_entropy(logits, batch["labels"])
    metrics = {"ce": loss, **aux}
    aux = aux["aux"]
    if cfg.mtp_depth and "tokens" in batch:
        loss_mtp = _mtp_loss(params, cfg, h, batch)
        metrics["mtp"] = loss_mtp
        loss = loss + 0.3 * loss_mtp
    total = loss + aux
    metrics["loss"] = total
    return total, metrics


def _mtp_loss(params: Params, cfg: ModelConfig, h, batch):
    """DeepSeek-V3 multi-token prediction: one extra block predicting
    token t+2 from [h_t ; embed(token_{t+1})]."""
    dt = h.dtype
    emb = params["embed"]["w"].astype(dt)[batch["tokens"]]
    nxt = jnp.roll(emb, -1, axis=1)
    u = jnp.concatenate([L.rms_norm(h, params["mtp"]["norm"]["w"],
                                    cfg.norm_eps), nxt], axis=-1)
    hm = jnp.einsum("bse,ed->bsd", u, params["mtp"]["proj"]["w"].astype(dt))
    hm, _, _ = _apply_layer(hm, params["mtp"]["block"], cfg,
                            LayerKind(mixer="attn"), None, None)
    logits = _logits(params, cfg, hm)
    labels2 = jnp.roll(batch["labels"], -1, axis=1)
    labels2 = labels2.at[:, -2:].set(-1)
    return cross_entropy(logits, labels2)


def serve_step(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
               cache, index) -> Tuple[jax.Array, Any]:
    """Prefill (S>1, index=0) or decode (S=1) against a persistent cache.
    Returns (logits[B,S,V], new_cache)."""
    h = _embed_inputs(params, cfg, batch)
    h, new_cache, _ = _run_stack(params, cfg, h, cache=cache, index=index)
    return _logits(params, cfg, h), new_cache
