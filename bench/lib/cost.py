"""Operations and bytes that each measured kernel, and the model step,
need, computed from shapes.  Counts are of the algorithm's need, not of
what a kernel happens to do: padding, re-reads and recomputation do not
count, so a share of the roofline built on them can only read low."""

from __future__ import annotations

from typing import Dict, Sequence, Tuple

SEED_BYTES = 12          # (lsn u64, size u32) prefix of every record hash


def hash_bytes(sizes: Sequence[int]) -> int:
    """HBM bytes the integrity hash must read for records of ``sizes``:
    the 12-byte seed and the payload, unpadded, once each."""
    return sum(SEED_BYTES + int(s) for s in sizes)


def ssd_forward_cost(operands: Sequence[Tuple[str, Tuple[int, ...]]]
                     ) -> Tuple[float, float]:
    """(flops, bytes) of one call of the SSD forward kernel, from its
    operands ``xdt [B,H,nc,Q,P], a [B,H,nc,Q,1], a_row [B,H,nc,1,Q],
    B [B,G,nc,Q,N], C [B,G,nc,Q,N]``.

    FLOPs are the four matrix products: C·Bᵀ (2Q²N) once per (batch,
    group, chunk), since the heads of a group share it, and per (batch,
    head, chunk) (C·Bᵀ∘L)·X (2Q²P), C·h (2QNP) and the chunk state Bᵀ·X
    (2QNP); the elementwise decay terms are left out.  Bytes are each
    input read once (B and C once per group, not per head) plus the
    outputs: y [B,H,nc,Q,P] in the dtype of xh (bf16) and the final
    state [B,H,N,P] f32."""
    (_, (b, h, nc, q, p)), (_, a_dims), _, (bdt, (_, g, _, _, n)), _ = \
        operands
    size = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4}
    xb = size.get(operands[0][0], 4)
    flops = b * g * nc * 2 * q * q * n \
        + b * h * nc * (2 * q * q * p + 4 * q * n * p)
    reads = (b * h * nc * q * p * xb            # xdt
             + 2 * b * h * nc * q * 4           # a, a_row
             + 2 * b * g * nc * q * n * size.get(bdt, 4))  # B, C
    writes = b * h * nc * q * p * 2 + b * h * n * p * 4
    return float(flops), float(reads + writes)


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peaks: Dict) -> Tuple[float, str]:
    """Least time the chip could take over the time taken, in %, and
    which bound sets that least time."""
    t_flops = flops / peaks["bf16_flops"]
    t_bytes = nbytes / peaks["hbm_bytes_s"]
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound


def mamba2_flops_per_token(cfg: Dict) -> float:
    """Forward + backward model FLOPs per token (3x the forward; the
    rematerialised forward is not counted) of a Mamba2 stack from the
    configuration's shapes.

    Forward per layer and token: in_proj 2·D·(2·di + 2·G·N + H), the
    depthwise conv 2·W·(di + 2·G·N), the SSD chunk scan at chunk Q —
    C·Bᵀ once per group (2·Q·N·G), and per head the masked product with
    X (2·Q·P) and the two state products (4·N·P) — and out_proj
    2·di·D.  Plus the tied output head 2·D·V.  Norms, gates and the
    loss are left out."""
    d = cfg["d_model"]
    di = cfg["expand"] * d
    p = cfg["headdim"]
    h = di // p
    n = cfg["d_state"]
    g = cfg["ngroups"]
    w = cfg["d_conv"]
    q = cfg["chunk_size"]
    v = cfg["vocab_size"]
    per_layer = (2 * d * (2 * di + 2 * g * n + h)
                 + 2 * w * (di + 2 * g * n)
                 + 2 * q * n * g + h * (2 * q * p + 4 * n * p)
                 + 2 * di * d)
    fwd = cfg["n_layer"] * per_layer + 2 * d * v
    return 3.0 * fwd
