"""Pallas TPU flash attention (forward) with causal / sliding-window
masks, logit softcap, and GQA head grouping.

TPU adaptation notes (vs the CUDA FlashAttention recipe):
  * grid = (batch·heads, q_blocks, k_blocks), k innermost — the TPU core
    walks k blocks sequentially, so the online-softmax running state
    (m, l, acc) lives in VMEM scratch across k steps; no shared-memory
    tile double-buffering to manage (Pallas pipelines HBM→VMEM copies
    automatically from the BlockSpecs);
  * (bq × bk) = (256 × 512) tiles: both MXU-aligned (128 multiples);
    scores fp32 in-register, accumulator fp32, inputs bf16;
  * GQA: the kv BlockSpec index_map folds h -> h // (H/KV), streaming
    each kv head once per query-head group without materializing the
    repeat (same trick as the SSD kernel's group handling);
  * causal/window masking is done by iota comparison in-register; fully
    out-of-range k blocks are skipped with pl.when (no MXU issue).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
DEFAULT_BQ = 256
DEFAULT_BK = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  causal, window, cap, scale, nk, bq, bk):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    # causal/window block-level skip: block fully masked -> no compute
    needed = True
    if causal:
        needed = k_start <= q_start + bq - 1
    if window is not None:
        needed = jnp.logical_and(
            needed, k_start + bk - 1 > q_start - window) if causal else \
            (k_start + bk - 1 > q_start - window)

    @pl.when(needed)
    def _step():
        q = q_ref[0].astype(jnp.float32)          # [bq, D]
        k = k_ref[0].astype(jnp.float32)          # [bk, D]
        v = v_ref[0].astype(jnp.float32)          # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if cap is not None:
            s = jnp.tanh(s / cap) * cap
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            ok = jnp.logical_and(ok, kpos <= qpos)
        if window is not None:
            ok = jnp.logical_and(ok, kpos > qpos - window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]                        # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _emit():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True,
                           window: Optional[int] = None,
                           cap: Optional[float] = None,
                           scale: Optional[float] = None,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q [B,H,S,D]; k,v [B,KV,S,D] -> [B,H,S,D]."""
    B, H, S, D = q.shape
    KV = k.shape[1]
    rep = H // KV
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * KV, S, D)
    vf = v.reshape(B * KV, S, D)

    def kv_map(bh, qi, ki, rep=rep, KV=KV):
        b = bh // (KV * rep)
        h = bh % (KV * rep)
        return (b * KV + h // rep, ki, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, causal=causal, window=window,
                          cap=cap, scale=scale, nk=nk, bq=bq, bk=bk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, D), kv_map),
            pl.BlockSpec((1, bk, D), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, D), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
