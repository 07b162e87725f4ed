"""Neural layers shared by all ten architectures (pure JAX / XLA ops).

Everything here lowers to einsum/scan/scatter so the multi-pod dry-run
can compile for 512 host devices; the Pallas kernels in
``repro.kernels`` are drop-in accelerated equivalents validated against
these (see kernels/*/ref.py).

Attention comes in four execution strategies, chosen by shape:
  * direct      — materialized scores (short sequences, decode).
  * flash       — long global causal self-attention on the TPU: the
                  bundled splash kernel (Pallas, with its backward
                  kernels), which skips key blocks above the diagonal
                  and keeps score tiles in VMEM.
  * blockwise   — q-chunked lazy softmax against full K/V, each chunk
                  checkpointed (every other long global call; memory
                  O(chunk·S)).
  * sliding     — banded gather per query chunk (local layers: O(S·w)
                  compute instead of O(S²) — gemma2's local half).
"""

from __future__ import annotations

import math
import os
from functools import lru_cache, partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .config import ModelConfig

# ---------------------------------------------------------------------- #
# numerics helpers
# ---------------------------------------------------------------------- #

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * (1.0 + w.astype(jnp.float32))).astype(dt)


def softcap(x: jax.Array, cap: Optional[float]) -> jax.Array:
    if cap is None:
        return x
    return jnp.tanh(x / cap) * cap


def rope_tables(positions: jax.Array, dim: int, theta: float
                ) -> Tuple[jax.Array, jax.Array]:
    """positions [..., S] -> cos/sin tables [..., S, dim/2] (fp32)."""
    inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [..., S, H, D]; cos/sin [..., S, D/2] broadcast over heads."""
    dt = x.dtype
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c, s = cos[..., None, :], sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s],
                           axis=-1).astype(dt)


# ---------------------------------------------------------------------- #
# attention strategies
# ---------------------------------------------------------------------- #
NEG_INF = -2.0 ** 30


def _mask_bias(qi: jax.Array, ki: jax.Array, causal: bool,
               window: Optional[int], kv_len: Optional[jax.Array]
               ) -> jax.Array:
    """Additive fp32 bias [..., q, k] from absolute indices."""
    ok = jnp.ones((qi.shape[-1], ki.shape[-1]), dtype=bool)
    if causal:
        ok &= ki[None, :] <= qi[:, None]
    if window is not None:
        ok &= ki[None, :] > qi[:, None] - window
    if kv_len is not None:
        ok &= ki[None, :] < kv_len
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _direct_attention(q, k, v, *, scale, causal, window, cap,
                      q_offset, kv_len):
    """q [B,Sq,K,G,D]; k,v [B,Sk,K,D] -> [B,Sq,K,G,D]."""
    B, Sq = q.shape[0], q.shape[1]
    Sk = k.shape[1]
    s = jnp.einsum("bqkgd,btkd->bkgqt", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = softcap(s, cap)
    qi = q_offset + jnp.arange(Sq)
    ki = jnp.arange(Sk)
    s = s + _mask_bias(qi, ki, causal, window, kv_len)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqt,btkd->bqkgd", p, v)


def _softmax_rows(s):
    """Softmax over the last axis, stabilised by the row's maximum held
    behind an optimization barrier: fused with the subtraction, XLA's
    TPU compiler turns the maximum into a reduce-window as wide as the
    row at every position, quadratic in the key length (1.5 s a pass of
    a 32-head layer over 2 x 8192 positions on a TPU v5e)."""
    m = lax.optimization_barrier(
        lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True)))
    e = jnp.exp(s - m)
    return e / jnp.sum(e, axis=-1, keepdims=True)


def _blockwise_attention(q, k, v, *, scale, causal, window, cap,
                         q_offset, chunk_q, unroll):
    """q-chunked attention against full K/V (memory O(chunk_q × Sk)).

    Each q-step is jax.checkpoint'ed so the backward pass recomputes its
    score tile instead of saving S² probabilities.  The causal half-waste
    (masked tiles still computed) is inherent to the XLA path; on the TPU
    ``_flash_attention`` skips fully-masked tiles.
    """
    B, Sq, K, G, D = q.shape
    Dv = v.shape[-1]
    nq = Sq // chunk_q
    qc = jnp.moveaxis(q.reshape(B, nq, chunk_q, K, G, D), 1, 0)

    def q_step(_, qi_blk):
        qblk, qidx = qi_blk                       # [B,cq,K,G,D], scalar
        s = jnp.einsum("bqkgd,btkd->bkgqt", qblk, k,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cap)
        qpos = q_offset + qidx * chunk_q + jnp.arange(chunk_q)
        kpos = jnp.arange(k.shape[1])
        s = s + _mask_bias(qpos, kpos, causal, window, None)
        p = _softmax_rows(s).astype(v.dtype)
        out = jnp.einsum("bkgqt,btkd->bqkgd", p, v)
        return None, out

    body = jax.checkpoint(q_step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = lax.scan(body, None, (qc, jnp.arange(nq)),
                       unroll=True if unroll else 1)
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, K, G, Dv).astype(q.dtype)


def _sliding_attention(q, k, v, *, scale, window, cap, chunk_q, unroll):
    """Banded local attention: each query chunk sees only [start-w, end).
    O(S·w) compute instead of O(S²) — gemma2's local layers."""
    B, Sq, K, G, D = q.shape
    Dv = v.shape[-1]
    nq = Sq // chunk_q
    band = window + chunk_q               # kv slab per query chunk
    # left-pad K/V so every slab read is in bounds
    kp = jnp.pad(k, ((0, 0), (band - chunk_q, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (band - chunk_q, 0), (0, 0), (0, 0)))
    qc = jnp.moveaxis(q.reshape(B, nq, chunk_q, K, G, D), 1, 0)

    def q_step(_, qi_blk):
        qblk, qidx = qi_blk
        start = qidx * chunk_q            # slab covers [start-w, start+cq)
        kblk = lax.dynamic_slice_in_dim(kp, start, band, axis=1)
        vblk = lax.dynamic_slice_in_dim(vp, start, band, axis=1)
        s = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kblk,
                       preferred_element_type=jnp.float32) * scale
        s = softcap(s, cap)
        qpos = start + jnp.arange(chunk_q)
        kpos = start - window + jnp.arange(band)   # absolute (pre-pad) index
        ok = (kpos[None, :] <= qpos[:, None]) & \
             (kpos[None, :] > qpos[:, None] - window) & (kpos[None, :] >= 0)
        s = s + jnp.where(ok, 0.0, NEG_INF)
        p = jax.nn.softmax(s, axis=-1).astype(vblk.dtype)
        out = jnp.einsum("bkgqt,btkd->bqkgd", p, vblk)
        return None, out

    body = jax.checkpoint(q_step,
                          policy=jax.checkpoint_policies.nothing_saveable)
    _, outs = lax.scan(body, None, (qc, jnp.arange(nq)),
                       unroll=True if unroll else 1)
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, K, G, Dv).astype(q.dtype)


def _flash_block(q, k, *, causal, window, cap, q_offset, kv_len
                 ) -> Optional[int]:
    """Block size of the flash path for this call, or None where the call
    keeps the XLA paths: off the TPU, and for anything but global causal
    self-attention (no window, soft cap or cache length, queries from
    position 0, as many keys as queries) over a length that one of the
    blocks divides."""
    Sq, Sk = q.shape[1], k.shape[1]
    if (jax.default_backend() != "tpu" or not causal or window is not None
            or cap is not None or kv_len is not None
            or not (isinstance(q_offset, int) and q_offset == 0)
            or Sq != Sk):
        return None
    return next((b for b in (1024, 512, 256) if Sq % b == 0), None)


@lru_cache(maxsize=None)
def _splash_kernel(heads: int, seq: int, block: int, interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * heads)
    sizes = sk.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        block_q_dq=block, block_kv_dq=block)
    with jax.ensure_compile_time_eval():     # cached: concrete, not traced
        return sk.make_splash_mha_single_device(mask, block_sizes=sizes,
                                                interpret=interpret)


def _flash_attention(q, k, v, *, scale, block, interpret=False):
    """Causal self-attention through the splash kernel: q [B,S,K,G,D];
    k,v [B,S,K,D] -> [B,S,K,G,D].  Query head k·G+g reads KV head k, the
    kernel's own grouping; scores accumulate in f32 with f32 softmax
    statistics.  The kernel takes no scale, so q is scaled in f32 and
    rounded once to its dtype."""
    B, S, K, G, D = q.shape
    qh = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qh = jnp.moveaxis(qh.reshape(B, S, K * G, D), 1, 2)
    kh, vh = jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2)
    o = jax.vmap(_splash_kernel(K * G, S, block, interpret))(qh, kh, vh)
    return jnp.moveaxis(o, 2, 1).reshape(B, S, K, G, v.shape[-1])


def attention(q, k, v, *, causal=True, window=None, cap=None, q_offset=0,
              kv_len=None, chunk_q=512, scale=None, unroll=False):
    """Dispatch on shape: decode/short -> direct; long global causal on
    the TPU -> flash; long local -> sliding; other long global ->
    q-chunked lazy softmax."""
    B, Sq, K, G, D = q.shape
    Sk = k.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if Sq == 1 or (Sq * Sk) <= (2048 * 2048) or kv_len is not None:
        return _direct_attention(q, k, v, scale=scale, causal=causal,
                                 window=window, cap=cap, q_offset=q_offset,
                                 kv_len=kv_len)
    block = _flash_block(q, k, causal=causal, window=window, cap=cap,
                         q_offset=q_offset, kv_len=kv_len)
    if block:
        return _flash_attention(q, k, v, scale=scale, block=block)
    if window is not None and Sq % chunk_q == 0 and Sq > window:
        return _sliding_attention(q, k, v, scale=scale, window=window,
                                  cap=cap, chunk_q=chunk_q, unroll=unroll)
    if Sq % chunk_q == 0:
        return _blockwise_attention(q, k, v, scale=scale, causal=causal,
                                    window=window, cap=cap,
                                    q_offset=q_offset, chunk_q=chunk_q,
                                    unroll=unroll)
    return _direct_attention(q, k, v, scale=scale, causal=causal,
                             window=window, cap=cap, q_offset=q_offset,
                             kv_len=kv_len)


# ---------------------------------------------------------------------- #
# GQA attention layer (dense archs, jamba's attn layers)
# ---------------------------------------------------------------------- #

def gqa_params_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, \
        cfg.resolved_head_dim
    shapes = {
        "wq": (D, KV, H // KV, hd),
        "wk": (D, KV, hd),
        "wv": (D, KV, hd),
        "wo": (KV, H // KV, hd, D),
    }
    if cfg.qkv_bias:
        shapes.update({"bq": (KV, H // KV, hd), "bk": (KV, hd),
                       "bv": (KV, hd)})
    return shapes


def gqa_attention(x, p, cfg: ModelConfig, *, local: bool,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  index: Optional[jax.Array] = None):
    """x [B,S,D].  cache = {"k","v" [B,T,KV,hd]} for serving; ``index`` is
    the global write position (0 at prefill).  Returns (y, new_cache)."""
    B, S, D = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bsd,dkgh->bskgh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if cfg.use_rope:
        pos0 = 0 if index is None else index
        positions = (pos0 + jnp.arange(S))[None, :]
        cos, sin = rope_tables(positions, hd, cfg.rope_theta)
        q = apply_rope(q.reshape(B, S, -1, hd), cos, sin).reshape(q.shape)
        k = apply_rope(k, cos, sin)
    window = cfg.sliding_window if local else None
    if cache is None:
        o = attention(q, k, v, causal=cfg.causal, window=window,
                      cap=cfg.attn_logit_softcap, chunk_q=cfg.attn_chunk_q,
                      unroll=cfg.scan_unroll)
        new_cache = None
    else:
        ck = lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), index, axis=1)
        cv = lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), index, axis=1)
        if S > 1:
            # prefill (index==0 by construction): attend within the new
            # span directly — blockwise kicks in for long S, and we skip
            # the still-empty tail of the cache buffer.
            o = attention(q, k, v, causal=cfg.causal, window=window,
                          cap=cfg.attn_logit_softcap,
                          chunk_q=cfg.attn_chunk_q, unroll=cfg.scan_unroll)
        else:
            o = attention(q, ck, cv, causal=False, window=window,
                          cap=cfg.attn_logit_softcap, q_offset=index,
                          kv_len=index + S)
        new_cache = {"k": ck, "v": cv}
    y = jnp.einsum("bskgh,kghd->bsd", o, p["wo"])
    return y, new_cache


def gqa_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    hd = cfg.resolved_head_dim
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "k": jax.ShapeDtypeStruct((batch, max_len, cfg.n_kv_heads, hd), dt),
        "v": jax.ShapeDtypeStruct((batch, max_len, cfg.n_kv_heads, hd), dt),
    }


# ---------------------------------------------------------------------- #
# MLA attention (deepseek-v3): low-rank Q/KV with compressed cache
# ---------------------------------------------------------------------- #

def mla_params_shapes(cfg: ModelConfig) -> Dict[str, Tuple[int, ...]]:
    D, H = cfg.d_model, cfg.n_heads
    qr, kr = cfg.q_lora_rank, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    return {
        "wq_a": (D, qr), "q_norm": (qr,),
        "wq_b": (qr, H, dn + dr),
        "wkv_a": (D, kr + dr), "kv_norm": (kr,),
        "wkv_b": (kr, H, dn + dv),
        "wo_mla": (H, dv, D),
    }


def mla_attention(x, p, cfg: ModelConfig, *,
                  cache: Optional[Dict[str, jax.Array]] = None,
                  index: Optional[jax.Array] = None):
    """DeepSeek-V3 multi-head latent attention.  The serving cache stores
    only the compressed latent (kv_lora + rope dims) per token."""
    B, S, D = x.shape
    H = cfg.n_heads
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    kr = cfg.kv_lora_rank
    pos0 = 0 if index is None else index
    positions = (pos0 + jnp.arange(S))[None, :]

    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["wq_a"]), p["q_norm"],
                  cfg.norm_eps)
    q = jnp.einsum("bsr,rhe->bshe", cq, p["wq_b"])      # e = dn+dr
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    cos, sin = rope_tables(positions, dr, cfg.rope_theta)
    q_rope = apply_rope(q_rope, cos, sin)
    q_full = jnp.concatenate([q_nope, q_rope], axis=-1)

    ckv_full = jnp.einsum("bsd,de->bse", x, p["wkv_a"])  # [B,S,kr+dr]
    ckv, k_rope = ckv_full[..., :kr], ckv_full[..., kr:]
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    latent = jnp.concatenate(
        [rms_norm(ckv, p["kv_norm"], cfg.norm_eps), k_rope], axis=-1)

    new_cache = None
    if cache is not None:
        lat_buf = lax.dynamic_update_slice_in_dim(
            cache["latent"], latent.astype(cache["latent"].dtype), index,
            axis=1)
        new_cache = {"latent": lat_buf}
        lat = latent if S > 1 else lat_buf     # prefill: fresh span only
        kv_len = None if S > 1 else index + S
        q_offset = 0 if S > 1 else index
        causal = cfg.causal if S > 1 else False
    else:
        lat, kv_len, q_offset, causal = latent, None, 0, cfg.causal

    if cache is not None and S == 1 and cfg.mla_absorb:
        # Absorbed decode: fold wkv_b into the query/output projections so
        # attention runs directly in the compressed latent space — avoids
        # re-materializing K/V for the whole 32k+ cache every step.
        wkb = p["wkv_b"][..., :dn]                      # [kr,H,dn]
        wvb = p["wkv_b"][..., dn:]                      # [kr,H,dv]
        q_lat = jnp.einsum("bshe,rhe->bshr", q_nope, wkb)
        q_eff = jnp.concatenate([q_lat, q_rope], axis=-1)  # [B,1,H,kr+dr]
        o_lat = attention(
            q_eff.reshape(B, S, 1, H, kr + dr),
            lat[:, :, None, :],                          # KV=1, G=H
            lat[:, :, None, :kr],
            causal=False, q_offset=q_offset, kv_len=kv_len,
            scale=1.0 / math.sqrt(dn + dr))
        o = jnp.einsum("bshr,rhv->bshv", o_lat.reshape(B, S, H, kr), wvb)
        y = jnp.einsum("bshv,hvd->bsd", o, p["wo_mla"])
        return y, new_cache

    ckv_t, krope_t = lat[..., :kr], lat[..., kr:]
    kv = jnp.einsum("btr,rhe->bthe", ckv_t, p["wkv_b"])   # e = dn+dv
    k_nope, vv = kv[..., :dn], kv[..., dn:]
    # per-head keys [B,T,H,dn+dr]; treat heads as KV groups (G=1)
    k_full = jnp.concatenate(
        [k_nope, jnp.broadcast_to(krope_t[:, :, None, :],
                                  (*k_nope.shape[:3], dr))], axis=-1)
    o = attention(q_full.reshape(B, S, H, 1, dn + dr),
                  k_full, vv, causal=causal, q_offset=q_offset,
                  kv_len=kv_len, scale=1.0 / math.sqrt(dn + dr),
                  unroll=cfg.scan_unroll)
    o = o.reshape(B, S, H, dv)
    y = jnp.einsum("bshv,hvd->bsd", o, p["wo_mla"])
    return y, new_cache


def mla_cache_spec(cfg: ModelConfig, batch: int, max_len: int):
    dt = jnp.dtype(cfg.compute_dtype)
    width = cfg.kv_lora_rank + cfg.qk_rope_dim
    return {
        "latent": jax.ShapeDtypeStruct((batch, max_len, width), dt),
    }


# ---------------------------------------------------------------------- #
# FFN: dense (swiglu) + mixture of experts
# ---------------------------------------------------------------------- #

def mlp_params_shapes(cfg: ModelConfig, d_ff: int) -> Dict[str, Tuple]:
    D = cfg.d_model
    n_in = 2 if cfg.gated_mlp else 1
    shapes = {"wi": (D, n_in, d_ff), "wo": (d_ff, D)}
    if cfg.mlp_bias:
        shapes.update({"bi": (n_in, d_ff), "bo": (D,)})
    return shapes


def _act(x, kind: str):
    x = x.astype(jnp.float32)
    if kind == "relu2":
        return jnp.square(jax.nn.relu(x))
    f = jax.nn.gelu if kind == "gelu" else jax.nn.silu
    return f(x)


def _glu(h, cfg: ModelConfig, dtype):
    """h [..., n_in, F] -> activation [..., F]: act(gate) * up when the
    MLP is gated, else act(h)."""
    if cfg.gated_mlp:
        return _act(h[..., 0, :], cfg.mlp_act).astype(dtype) * h[..., 1, :]
    return _act(h[..., 0, :], cfg.mlp_act).astype(dtype)


def mlp(x, p, cfg: ModelConfig):
    h = jnp.einsum("bsd,dcf->bscf", x, p["wi"])
    if cfg.mlp_bias:
        h = h + p["bi"]
    act = _glu(h, cfg, x.dtype)
    y = jnp.einsum("bsf,fd->bsd", act, p["wo"])
    if cfg.mlp_bias:
        y = y + p["bo"]
    return y


def moe_params_shapes(cfg: ModelConfig) -> Dict[str, Tuple]:
    """The router scores all ``n_experts``; the expert weights are those
    of the experts this chip holds (all of them unless ``experts_held``)."""
    D, E, F = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    El = cfg.experts_held or E
    n_in = 2 if cfg.gated_mlp else 1
    shapes = {
        "router": (D, E),
        "experts": {"wi": (El, D, n_in, F), "wo": (El, F, D)},
    }
    if cfg.n_shared_experts:
        shapes["shared"] = mlp_params_shapes(
            cfg, cfg.moe_shared_d_ff or cfg.moe_d_ff * cfg.n_shared_experts)
    return shapes


def _route(xt, router, cfg: ModelConfig):
    """Scores [T,E], gates [T,K] (f32) and expert ids [T,K] of tokens
    ``xt [T,D]``.  Softmax scores are taken from a product in the compute
    dtype; sigmoid scores (DeepSeek-V3-style router) in float32, as the
    published router computes them (at the highest precision: at the
    default one the TPU takes a float32 product in one bfloat16 pass).
    The top-k gates are renormalised to sum 1 and then scaled by
    ``routed_scaling``.  The gates are taken from the scores through a
    one-hot product at the highest precision rather than top_k's values:
    the same numbers, and a backward pass without top_k's scatter, which
    the TPU runs slowly."""
    hi = lax.Precision.HIGHEST
    if cfg.router_score == "sigmoid":
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32),
                            router.astype(jnp.float32), precision=hi)
        scores = jax.nn.sigmoid(logits)
    else:
        logits = jnp.einsum("td,de->te", xt, router,
                            preferred_element_type=jnp.float32)
        scores = jax.nn.softmax(logits, axis=-1)
    _, gidx = lax.top_k(lax.stop_gradient(scores), cfg.experts_per_token)
    gate = jnp.einsum("tke,te->tk", jax.nn.one_hot(
        gidx, cfg.n_experts, dtype=scores.dtype), scores, precision=hi)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        gate = gate * cfg.routed_scaling
    return scores, gate, gidx


def _held_ids(gidx, cfg: ModelConfig):
    """Expert ids [T,K] -> flat local ids of the held experts, with
    ``El`` (one past the last held expert) for every other expert."""
    El = cfg.experts_held or cfg.n_experts
    local = gidx.reshape(-1) - cfg.expert_offset
    return jnp.where((local >= 0) & (local < El), local, El), El


def _switch_aux(scores, gidx, cfg: ModelConfig):
    """Switch-style load-balance auxiliary over all scored experts."""
    if not cfg.router_aux_weight:
        return jnp.zeros((), jnp.float32)
    E, T = cfg.n_experts, scores.shape[0]
    me = scores.mean(axis=0)                                   # [E]
    ce = jnp.zeros(E).at[gidx.reshape(-1)].add(1.0) / (T * gidx.shape[1])
    return cfg.router_aux_weight * E * jnp.sum(me * ce)


# -- expert parallelism (shard_map all-to-all dispatch) ----------------- #
# Set by the launcher: (mesh, axes) where experts are sharded over the
# flattened ``axes`` (data-major order, matching lax.all_to_all).  The
# pjit-native scatter formulation below is correct but GSPMD cannot
# shard its scatter across an expert-sharded buffer (it replicates the
# [T·K, D] gather — §Perf cell B measured 240 GB/dev fp32 all-reduces),
# so real EP uses the explicit a2a path.
_EP_STATE: Optional[Tuple[Any, Tuple[str, ...]]] = None


def set_moe_ep(mesh, axes: Optional[Tuple[str, ...]]) -> None:
    global _EP_STATE
    _EP_STATE = (mesh, tuple(axes)) if axes else None


def _moe_ep_applicable(x, cfg: ModelConfig) -> bool:
    if _EP_STATE is None:
        return False
    mesh, axes = _EP_STATE
    sizes = dict(mesh.shape)
    if any(a not in sizes for a in axes):
        return False
    d0, m = sizes[axes[0]], sizes[axes[1]]
    B, S, _ = x.shape
    return (B % d0 == 0 and S % m == 0 and
            cfg.n_experts % (d0 * m) == 0)


def _moe_ffn_ep(x, p, cfg: ModelConfig):
    """Expert-parallel MoE: routing at the pjit level; dispatch/compute/
    combine inside shard_map with two all_to_alls over the flattened
    (data, model) grid — each rank owns E/R experts and T/R tokens.
    Returns (y, aux)."""
    import math as _math
    from jax.sharding import PartitionSpec as P

    mesh, axes = _EP_STATE
    sizes = dict(mesh.shape)
    Dz, Mz = sizes[axes[0]], sizes[axes[1]]
    R = Dz * Mz
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    E_loc = E // R
    T_loc = (B // Dz) * (S // Mz)
    C = max(1, int(_math.ceil(T_loc * K / E * cfg.capacity_factor)))

    # routing at the pjit level (router grads flow through pjit normally)
    logits = jnp.einsum("bsd,de->bse", x, p["router"],
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, gidx = lax.top_k(probs, K)
    gate = (gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
            ).astype(x.dtype)

    # partition tokens over BOTH grid axes: [B, S, ...] -> [B, M, S/M, ...]
    def grid(v):
        return v.reshape(B, Mz, S // Mz, *v.shape[2:])
    xg, gig, gag = grid(x), grid(gidx), grid(gate)
    spec4 = P(axes[0], axes[1], None, None)
    spec_wi = P((axes[0], axes[1]), None, None, None)
    spec_wo = P((axes[0], axes[1]), None, None)

    def body(xl, gil, gal, wi, wo):
        xt = xl.reshape(-1, D)                       # [T_loc, D]
        gi = gil.reshape(-1, K)
        ga = gal.reshape(-1, K)
        flat_e = gi.reshape(-1)
        order = jnp.argsort(flat_e)
        sorted_e = flat_e[order]
        tok = order // K
        starts = jnp.searchsorted(sorted_e, jnp.arange(E))
        pos = jnp.arange(T_loc * K) - starts[sorted_e]
        dest = sorted_e // E_loc                     # target rank
        slot = (sorted_e % E_loc) * C + jnp.where(pos < C, pos,
                                                  E_loc * C)  # drop
        send = jnp.zeros((R, E_loc * C, D), xt.dtype)
        send = send.at[dest, slot].set(xt[tok], mode="drop")
        recv = lax.all_to_all(send, axes, split_axis=0, concat_axis=0)
        h = recv.reshape(R, E_loc, C, D).transpose(1, 0, 2, 3) \
            .reshape(E_loc, R * C, D)
        a = jnp.einsum("ecd,edgf->ecgf", h, wi)
        act = jax.nn.silu(a[..., 0, :].astype(jnp.float32)
                          ).astype(h.dtype) * a[..., 1, :]
        o = jnp.einsum("ecf,efd->ecd", act, wo)
        outb = o.reshape(E_loc, R, C, D).transpose(1, 0, 2, 3) \
            .reshape(R, E_loc * C, D)
        back = lax.all_to_all(outb, axes, split_axis=0, concat_axis=0)
        flatb = back.reshape(R * E_loc * C, D)
        idx = jnp.where(pos < C, dest * (E_loc * C) + slot,
                        R * E_loc * C)
        vals = flatb.at[idx].get(mode="fill", fill_value=0.0)
        vals = vals * ga.reshape(-1)[order][:, None]
        y = jnp.zeros((T_loc, D), xt.dtype).at[tok].add(vals)
        return y.reshape(xl.shape)

    y = jax.shard_map(body, mesh=mesh,
                      in_specs=(spec4, spec4, spec4, spec_wi, spec_wo),
                      out_specs=spec4, check_vma=False)(
        xg, gig, gag, p["experts"]["wi"], p["experts"]["wo"])
    y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp(x, p["shared"], cfg)
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros(E).at[gidx.reshape(-1)].add(1.0) / (B * S * K)
    aux = cfg.router_aux_weight * E * jnp.sum(me * ce)
    return y, aux


def moe_ffn(x, p, cfg: ModelConfig):
    """Sort-based dropped-token MoE (capacity factor ``cf``).

    Dispatch uses argsort + scatter (data movement, ~0 FLOPs in HLO)
    into per-expert capacity buckets, then batched expert einsums — so
    compiled FLOPs ≈ active FLOPs × cf, not × n_experts (the dense
    one-hot dispatch pathology).  With EP enabled (set_moe_ep) and a
    compatible shape, dispatch runs as shard_map all-to-alls instead.
    With ``experts_held`` only the held experts compute, each up to the
    capacity; pairs routed elsewhere are left out.
    Returns (y, aux_loss, rows computed per held expert | None for EP).
    """
    if _moe_ep_applicable(x, cfg):
        y, aux = _moe_ffn_ep(x, p, cfg)
        return y, aux, None
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)
    probs, gate, gidx = _route(xt, p["router"], cfg)   # [T,K]

    flat_e, El = _held_ids(gidx, cfg)                  # [T*K]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    tok = order // K
    starts = jnp.searchsorted(sorted_e, jnp.arange(El))
    pos = jnp.arange(T * K) - starts[jnp.minimum(sorted_e, El - 1)]
    C = max(1, int(math.ceil(T * K / E * cfg.capacity_factor)))
    pos = jnp.where((pos < C) & (sorted_e < El), pos, C)   # C => dropped

    buf = jnp.zeros((El, C, D), x.dtype)
    buf = buf.at[sorted_e, pos].set(xt[tok], mode="drop")
    h = jnp.einsum("ecd,edgf->ecgf", buf, p["experts"]["wi"])
    act = _glu(h, cfg, x.dtype)
    out_buf = jnp.einsum("ecf,efd->ecd", act, p["experts"]["wo"])

    contrib = out_buf.at[sorted_e, pos].get(mode="fill", fill_value=0.0)
    contrib = contrib * gate.reshape(-1)[order][:, None].astype(x.dtype)
    y = jnp.zeros((T, D), x.dtype).at[tok].add(contrib)
    y = y.reshape(B, S, D)

    if cfg.n_shared_experts:
        y = y + mlp(x, p["shared"], cfg)
    load = jnp.minimum(
        jnp.zeros(El, jnp.int32).at[flat_e].add(1, mode="drop"), C)
    return y, _switch_aux(probs, gidx, cfg), load


# -- dropless held experts (grouped matrix products) -------------------- #
def _gmm_tile(d: int) -> int:
    """The largest multiple of 128 up to 1024 that pads ``d`` by at most
    4%; ``d`` itself below 128."""
    if d < 128:
        return d
    for t in range(1024, 127, -128):
        if -(-d // t) * t <= 1.04 * d:
            return t
    return 128


def _gmm_tiles(m: int, k: int, n: int) -> Tuple[int, int, int]:
    return (512 if m % 512 == 0 else 128), _gmm_tile(k), _gmm_tile(n)


def _gmm(lhs, rhs, sizes):
    """Grouped product: rows ``sum(sizes[:g])`` to ``sum(sizes[:g+1])`` of
    ``lhs [M,K]`` times ``rhs[g] [K,N]``; rows past ``sum(sizes)`` are
    undefined.  Megablox's Pallas kernel on TPU (or with
    REPRO_USE_PALLAS=1, interpreted), ``lax.ragged_dot`` elsewhere."""
    use = os.environ.get("REPRO_USE_PALLAS") == "1" or \
        jax.default_backend() == "tpu"
    if use:
        from jax.experimental.pallas.ops.tpu.megablox import gmm
        return gmm(lhs, rhs, sizes, lhs.dtype, _gmm_tiles, None, None,
                   False, jax.default_backend() != "tpu")
    return lax.ragged_dot(lhs, rhs, sizes, preferred_element_type=lhs.dtype)


@partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _dispatch(xt, order, inv, M: int, K: int):
    """Rows ``xt[order[:M] // K]``: the tokens of the first M (token,
    slot) pairs in ``order``, a permutation of the T*K pairs whose
    inverse is ``inv``.  Its backward pass is a gather too: a token's
    gradient is the sum over its K pairs' rows, found through ``inv``
    (autodiff would scatter-add, which the TPU runs slowly)."""
    return xt[order[:M] // K]


def _dispatch_fwd(xt, order, inv, M, K):
    return _dispatch(xt, order, inv, M, K), inv


def _dispatch_bwd(M, K, inv, g):
    gpad = jnp.pad(g, ((0, inv.shape[0] - M), (0, 0)))
    gx = gpad[inv].reshape(-1, K, g.shape[-1]).astype(jnp.float32).sum(1)
    return gx.astype(g.dtype), None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _undispatch(o, order, inv, M: int):
    """Rows of ``o [M, D]`` back in (token, slot) order, [T*K, D]: pair p
    takes row inv[p], or zeros where inv[p] >= M.  Its backward pass is
    the gather by ``order``."""
    return jnp.pad(o, ((0, inv.shape[0] - M), (0, 0)))[inv]


def _undispatch_fwd(o, order, inv, M):
    return _undispatch(o, order, inv, M), order


def _undispatch_bwd(M, order, g):
    return g[order[:M]], None, None


_undispatch.defvjp(_undispatch_fwd, _undispatch_bwd)


def moe_held_ffn(x, p, cfg: ModelConfig):
    """Dropless MoE over the experts this chip holds.

    The router scores all ``n_experts`` and picks each token's top k;
    the (token, expert) pairs whose expert is held are sorted by expert
    and each held expert's rows go through two grouped matrix products
    (``_gmm``), the group sizes staying on the device.  The output is
    the held experts' gated part of the layer plus the shared expert:
    what the other chips' experts add is not computed here.  The row
    buffer is sized for the worst case, every token routed to
    min(k, held) held experts, so no row is ever dropped; its rows past
    the routed ones are held at zero (the grouped products leave them
    undefined), in both passes.  Rows move by gathers only.
    Returns (y, aux, rows per held expert [held])."""
    B, S, D = x.shape
    K = cfg.experts_per_token
    T = B * S
    xt = x.reshape(T, D)
    scores, gate, gidx = _route(xt, p["router"], cfg)
    local, El = _held_ids(gidx, cfg)                   # [T*K]
    sizes = jnp.sum(local[:, None] == jnp.arange(El), axis=0,
                    dtype=jnp.int32)
    M = -(-T * min(K, El) // 128) * 128
    Tp = max(T, -(-M // K))            # tokens, padded where T*K < M
    xp = jnp.pad(xt, ((0, Tp - T), (0, 0)))
    local = jnp.pad(local, (0, (Tp - T) * K), constant_values=El)
    order = jnp.argsort(local)                         # held pairs first
    inv = jnp.argsort(order)
    valid = (jnp.arange(M) < sizes.sum())[:, None]
    xs = jnp.where(valid, _dispatch(xp, order, inv, M, K), 0)
    wi, wo = p["experts"]["wi"], p["experts"]["wo"]
    F = wo.shape[1]
    h = jnp.where(valid, _gmm(xs, wi.reshape(El, D, -1), sizes), 0)
    act = _glu(h.reshape(M, -1, F), cfg, x.dtype)
    o = jnp.where(valid, _gmm(act, wo, sizes), 0)      # [M, D]
    pairs = _undispatch(o, order, inv, M)[:T * K].reshape(T, K, D)
    y = jnp.einsum("tkd,tk->td", pairs, gate.astype(o.dtype),
                   preferred_element_type=jnp.float32)
    y = y.astype(x.dtype).reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + mlp(x, p["shared"], cfg)
    return y, _switch_aux(scores, gidx, cfg), sizes


# ---------------------------------------------------------------------- #
# Mamba2 (SSD) mixer
# ---------------------------------------------------------------------- #

def ssm_dims(cfg: ModelConfig) -> Tuple[int, int, int, int]:
    if cfg.ssm_n_heads:
        n_heads = cfg.ssm_n_heads
        d_inner = n_heads * cfg.ssm_head_dim
        return d_inner, n_heads, cfg.ssm_head_dim, cfg.ssm_state_dim
    d_inner = cfg.ssm_expand * cfg.d_model
    n_heads = d_inner // cfg.ssm_head_dim
    return d_inner, n_heads, cfg.ssm_head_dim, cfg.ssm_state_dim


def ssm_params_shapes(cfg: ModelConfig) -> Dict[str, Tuple]:
    D = cfg.d_model
    di, nh, hd, ds = ssm_dims(cfg)
    G = cfg.ssm_n_groups
    conv_ch = di + 2 * G * ds
    return {
        "in_proj": (D, 2 * di + 2 * G * ds + nh),   # z, x, B, C, dt
        "conv_w": (cfg.ssm_conv_width, conv_ch),
        "conv_b": (conv_ch,),
        "A_log": (nh,),
        "D_skip": (nh,),
        "dt_bias": (nh,),
        "out_norm": (di,),
        "out_proj": (di, D),
    }


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv1d.  x [B,S,C]; w [W,C].  With ``state``
    ([B,W-1,C]) runs incrementally and returns the new state."""
    W = w.shape[0]
    if state is None:
        xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
        new_state = xp[:, -(W - 1):, :] if W > 1 else None
    else:
        xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
        new_state = xp[:, -(W - 1):, :]
    out = sum(xp[:, i : i + x.shape[1], :] * w[i] for i in range(W))
    return jax.nn.silu((out + b).astype(jnp.float32)).astype(x.dtype), \
        new_state


def ssm_mixer(x, p, cfg: ModelConfig,
              cache: Optional[Dict[str, jax.Array]] = None):
    """Mamba2 block mixer.  cache = {"conv" [B,W-1,C], "state" [B,H,P,N]}."""
    B, S, D = x.shape
    di, nh, hd, ds = ssm_dims(cfg)
    G = cfg.ssm_n_groups
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xi, Bm, Cm, dt = jnp.split(
        zxbcdt, [di, 2 * di, 2 * di + G * ds, 2 * di + 2 * G * ds], axis=-1)
    conv_in = jnp.concatenate([xi, Bm, Cm], axis=-1)
    conv_out, new_conv = _causal_conv(
        conv_in, p["conv_w"], p["conv_b"],
        state=None if cache is None else cache["conv"])
    xi, Bm, Cm = jnp.split(conv_out, [di, di + G * ds], axis=-1)
    xh = xi.reshape(B, S, nh, hd)
    Bm = Bm.reshape(B, S, G, ds)
    Cm = Cm.reshape(B, S, G, ds)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    from ..kernels.ssd_scan import ops as ssd_ops
    if cache is None or S > 1:
        # training or prefill: chunked SSD; final state seeds decoding
        y, final = ssd_ops.ssd(xh, dt, p["A_log"], Bm, Cm,
                               chunk=min(cfg.ssm_chunk, S))
        new_cache = None if cache is None else \
            {"conv": new_conv, "state": final}
    else:
        y, new_state = ssd_ops.ssd_decode(xh, dt, p["A_log"], Bm, Cm,
                                          cache["state"])
        new_cache = {"conv": new_conv, "state": new_state}
    y = y + xh * p["D_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, di)
    if cfg.ssm_gate_first:
        # gate, then RMSNorm over each B/C group's share of the width
        g = (y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32)))
        g = rms_norm(g.reshape(B, S, G, di // G),
                     p["out_norm"].reshape(G, di // G), cfg.norm_eps)
        y = g.reshape(B, S, di).astype(y.dtype)
    else:
        y = rms_norm(y, p["out_norm"], cfg.norm_eps)
        y = y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype)
    out = jnp.einsum("bse,ed->bsd", y, p["out_proj"])
    return out, new_cache


def ssm_cache_spec(cfg: ModelConfig, batch: int):
    di, nh, hd, ds = ssm_dims(cfg)
    G = cfg.ssm_n_groups
    conv_ch = di + 2 * G * ds
    dt = jnp.dtype(cfg.compute_dtype)
    return {
        "conv": jax.ShapeDtypeStruct(
            (batch, cfg.ssm_conv_width - 1, conv_ch), dt),
        "state": jax.ShapeDtypeStruct((batch, nh, hd, ds), jnp.float32),
    }
