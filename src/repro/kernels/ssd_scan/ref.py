"""Pure-jnp oracle for the Mamba2 SSD (state-space duality) chunked scan.

Discrete SSD recurrence per head (state h ∈ R^{P×N}):

    h_t = exp(a_t) · h_{t-1} + (dt_t · x_t) ⊗ B_t        a_t = -exp(A_log)·dt_t
    y_t = C_t · h_t

Chunked evaluation (chunk length Q, cumulative log-decay A_i within a
chunk):

    y_i = Σ_{j≤i} exp(A_i - A_j) (C_i·B_j) (dt_j x_j)     [intra, quadratic]
        + exp(A_i) C_i · h_chunk_start                    [inter, recurrent]

The chunk states are combined with a sequential scan over chunks (the
only serial dependency — O(S/Q) steps).
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


def ssd_reference(xh: jax.Array, dt: jax.Array, A_log: jax.Array,
                  Bm: jax.Array, Cm: jax.Array, chunk: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """xh [B,S,H,P]; dt [B,S,H] (post-softplus, fp32); A_log [H];
    Bm/Cm [B,S,G,N] (G groups shared across H//G heads each).
    Returns (y [B,S,H,P], final_state [B,H,P,N] fp32)."""
    B_, S, H, P = xh.shape
    G, N = Bm.shape[2], Bm.shape[3]
    Q = min(chunk, S)
    assert S % Q == 0, f"seq {S} not divisible by chunk {Q}"
    nc = S // Q
    rep = H // G

    dt32 = dt.astype(jnp.float32)
    a = (-jnp.exp(A_log.astype(jnp.float32))) * dt32          # [B,S,H]
    xc = xh.astype(jnp.float32).reshape(B_, nc, Q, H, P)
    dtc = dt32.reshape(B_, nc, Q, H)
    ac = a.reshape(B_, nc, Q, H)
    Brep = jnp.repeat(Bm.astype(jnp.float32).reshape(B_, nc, Q, G, N),
                      rep, axis=3)                            # [B,nc,Q,H,N]
    Crep = jnp.repeat(Cm.astype(jnp.float32).reshape(B_, nc, Q, G, N),
                      rep, axis=3)
    xdt = xc * dtc[..., None]                                 # [B,nc,Q,H,P]

    cum = jnp.cumsum(ac, axis=2)                              # A_i (inclusive)
    # intra-chunk: L[i,j] = exp(A_i - A_j), j <= i.  Mask before the
    # exp: above the diagonal A_i - A_j > 0 overflows to inf over a long
    # chunk, and inf under a masked where makes a NaN gradient.
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]       # [B,nc,i,j,H]
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.exp(jnp.where(tri[None, None, :, :, None], seg, -jnp.inf))
    s = jnp.einsum("bcihn,bcjhn->bchij", Crep, Brep)
    w = s * jnp.transpose(L, (0, 1, 4, 2, 3))                 # [B,nc,H,i,j]
    y_intra = jnp.einsum("bchij,bcjhp->bcihp", w, xdt)

    # chunk states: Σ_j exp(A_end - A_j) B_j ⊗ xdt_j
    total = cum[:, :, -1, :]                                  # [B,nc,H]
    decay_out = jnp.exp(total[:, :, None, :] - cum)           # [B,nc,Q,H]
    states = jnp.einsum("bcqhn,bcqhp->bchpn",
                        Brep * decay_out[..., None], xdt)

    def chunk_step(h0, inp):
        st, tot = inp
        h1 = h0 * jnp.exp(tot)[:, :, None, None] + st
        return h1, h0                                          # emit h at start

    final, h_prev = lax.scan(chunk_step, jnp.zeros((B_, H, P, N),
                                                   jnp.float32),
                             (jnp.moveaxis(states, 1, 0),
                              jnp.moveaxis(total, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                        # [B,nc,H,P,N]

    # inter-chunk: exp(A_i) C_i · h_start
    y_inter = jnp.einsum("bcqhn,bchpn->bcqhp",
                         Crep * jnp.exp(cum)[..., None], h_prev)
    y = (y_intra + y_inter).reshape(B_, S, H, P)
    return y.astype(xh.dtype), final


def ssd_decode_reference(xh: jax.Array, dt: jax.Array, A_log: jax.Array,
                         Bm: jax.Array, Cm: jax.Array, state: jax.Array
                         ) -> Tuple[jax.Array, jax.Array]:
    """One-token recurrence.  xh [B,1,H,P]; state [B,H,P,N] fp32."""
    G = Bm.shape[2]
    rep = xh.shape[2] // G
    dt32 = dt[:, 0].astype(jnp.float32)                        # [B,H]
    a = (-jnp.exp(A_log.astype(jnp.float32))) * dt32
    decay = jnp.exp(a)[:, :, None, None]
    Br = jnp.repeat(Bm[:, 0].astype(jnp.float32), rep, axis=1)  # [B,H,N]
    Cr = jnp.repeat(Cm[:, 0].astype(jnp.float32), rep, axis=1)
    xdt = xh[:, 0].astype(jnp.float32) * dt32[..., None]        # [B,H,P]
    new_state = state * decay + jnp.einsum("bhp,bhn->bhpn", xdt, Br)
    y = jnp.einsum("bhn,bhpn->bhp", Cr, new_state)
    return y[:, None].astype(xh.dtype), new_state


def ssd_sequential_oracle(xh, dt, A_log, Bm, Cm):
    """Token-by-token recurrence — the ground truth the chunked algorithm
    must match (used by kernel tests)."""
    B_, S, H, P = xh.shape
    N = Bm.shape[3]
    state = jnp.zeros((B_, H, P, N), jnp.float32)
    ys = []
    for t in range(S):
        y, state = ssd_decode_reference(
            xh[:, t : t + 1], dt[:, t : t + 1], A_log,
            Bm[:, t : t + 1], Cm[:, t : t + 1], state)
        ys.append(y)
    return jnp.concatenate(ys, axis=1), state
