"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

TPU adaptation (vs the paper's CUDA Mamba2 kernel):
  * grid = (batch, heads, n_chunks) with the chunk axis innermost —
    TPU grids execute sequentially per core, so the running state lives
    in a VMEM scratch that persists across chunk steps (no inter-block
    semaphores as on GPU);
  * per-step work is three MXU matmuls (C·Bᵀ, (s∘L)·X, C·h) on
    (Q×N)/(Q×P) tiles — Q and N are 128-multiples so the matmuls are
    systolic-array aligned; P=64 rides in half-lane tiles;
  * the decay matrix L is built in-register from the chunk-local prefix
    sum (masked sums over the triangular mask: the TPU lowers no
    cumsum), never touching HBM;
  * differentiable through a custom VJP whose backward pass is the VJP
    of the pure-jnp reference (ref.ssd_reference).

Grouped B/C (GQA-style G < H) is handled by the index_map — group
tensors are streamed once per head without materializing the repeat.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import ssd_reference


def _ssd_kernel(xdt_ref, a_ref, a_row_ref, B_ref, C_ref,   # inputs
                y_ref, state_out_ref,                     # outputs
                state,                                    # VMEM [N, P] f32
                *, n_chunks: int):
    c = pl.program_id(2)

    @pl.when(c == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)    # [Q, P]
    a = a_ref[0, 0, 0].astype(jnp.float32)        # [Q, 1] log-decays
    a_row = a_row_ref[0, 0, 0].astype(jnp.float32)  # [1, Q] same, as a row
    Bm = B_ref[0, 0, 0].astype(jnp.float32)       # [Q, N]
    Cm = C_ref[0, 0, 0].astype(jnp.float32)       # [Q, N]
    Q = xdt.shape[0]

    # inclusive prefix sums A_i as a column and as a row: masked sums
    # over the lower-triangular mask (the TPU lowers no cumsum)
    li = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    tri = lj <= li
    cum = jnp.sum(jnp.where(tri, a_row, 0.0), axis=1, keepdims=True)
    cum_row = jnp.sum(jnp.where(li <= lj, a, 0.0), axis=0,
                      keepdims=True)                    # [1, Q]
    total = jnp.sum(a, axis=0, keepdims=True)           # [1, 1]
    # intra-chunk: (C Bᵀ ∘ L) · xdt
    L = jnp.exp(jnp.where(tri, cum - cum_row, -jnp.inf))
    s = jnp.dot(Cm, Bm.T, preferred_element_type=jnp.float32)
    y = jnp.dot(s * L, xdt, preferred_element_type=jnp.float32)
    # inter-chunk: exp(A_i) C_i · h_start
    y += jnp.exp(cum) * jnp.dot(
        Cm, state[...], preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    # state update: h' = exp(total) h + Σ exp(total - A_j) B_j ⊗ xdt_j
    decay_out = jnp.exp(total - cum)
    chunk_state = jnp.dot((Bm * decay_out).T, xdt,
                          preferred_element_type=jnp.float32)   # [N, P]
    state[...] = jnp.exp(total) * state[...] + chunk_state

    @pl.when(c == n_chunks - 1)
    def _emit():
        state_out_ref[0, 0] = state[...]


def _ssd_forward(xh: jax.Array, dt: jax.Array, A_log: jax.Array,
                 Bm: jax.Array, Cm: jax.Array, chunk: int,
                 interpret: bool) -> Tuple[jax.Array, jax.Array]:
    B_, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0
    nc = S // Q
    rep = H // G

    dt32 = dt.astype(jnp.float32)
    a = (-jnp.exp(A_log.astype(jnp.float32))) * dt32           # [B,S,H]
    xdt = (xh.astype(jnp.float32) * dt32[..., None])
    # layouts: [B, H, nc, Q, *] so the chunk axis is a grid dim
    xdt = xdt.transpose(0, 2, 1, 3).reshape(B_, H, nc, Q, P)
    a_in = a.transpose(0, 2, 1).reshape(B_, H, nc, Q, 1)
    a_row = a_in.reshape(B_, H, nc, 1, Q)
    B_in = Bm.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B_, G, nc, Q, N)
    C_in = Cm.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(
        B_, G, nc, Q, N)

    grid = (B_, H, nc)
    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=nc),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, 1), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, Q), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
            pl.BlockSpec((1, 1, 1, Q, N),
                         lambda b, h, c, rep=rep: (b, h // rep, c, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, Q, P), lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B_, H, nc, Q, P), xh.dtype),
            jax.ShapeDtypeStruct((B_, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(xdt, a_in, a_row, B_in, C_in)
    y = y.reshape(B_, H, S, P).transpose(0, 2, 1, 3)
    # state comes out [B,H,N,P]; match ref's [B,H,P,N]
    return y, state.transpose(0, 1, 3, 2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(xh, dt, A_log, Bm, Cm, chunk, interpret):
    return _ssd_forward(xh, dt, A_log, Bm, Cm, chunk, interpret)


def _ssd_fwd(xh, dt, A_log, Bm, Cm, chunk, interpret):
    return (_ssd_forward(xh, dt, A_log, Bm, Cm, chunk, interpret),
            (xh, dt, A_log, Bm, Cm))


def _ssd_bwd(chunk, interpret, res, g):
    # backward pass: the VJP of the pure-jnp reference (same math)
    _, vjp = jax.vjp(lambda *args: ssd_reference(*args, chunk), *res)
    return vjp(g)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_pallas(xh: jax.Array, dt: jax.Array, A_log: jax.Array,
               Bm: jax.Array, Cm: jax.Array, chunk: int,
               interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """Same contract as ref.ssd_reference.  xh [B,S,H,P], dt [B,S,H],
    A_log [H], Bm/Cm [B,S,G,N] -> (y [B,S,H,P], state [B,H,P,N]).

    Differentiable: the forward pass is the kernel, the backward pass
    is the VJP of ``ref.ssd_reference``."""
    return _ssd(xh, dt, A_log, Bm, Cm, chunk, interpret)
