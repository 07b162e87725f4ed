"""Operations, bytes and readings of the Nemotron-H stage's training cell:
the model FLOPs of a window from the held experts' actual rows, the
grouped matrix products' need, and how the grouped-product kernels are
found in a device trace.

Counts are of the algorithm's need, not of what a kernel does: padded
tiles, rows past the routed ones, re-reads and the rematerialised
forward pass do not count, so a share built on them can only read low.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

from . import trace as tr
from .cost import roofline_share


def _n(cfg: Dict) -> Dict:
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    pat = cfg["hybrid_override_pattern"]
    return dict(D=cfg["hidden_size"], di=H * P, H=H, P=P,
                N=cfg["ssm_state_size"], G=cfg["n_groups"],
                W=cfg["conv_kernel"], Q=cfg["chunk_size"],
                V=cfg["vocab_size"], E=cfg["n_routed_experts_published"],
                held=cfg["n_routed_experts"],
                F=cfg["moe_intermediate_size"],
                Fs=cfg["moe_shared_expert_intermediate_size"],
                QH=cfg["num_attention_heads"], KV=cfg["num_key_value_heads"],
                hd=cfg["head_dim"], nM=pat.count("M"), nE=pat.count("E"),
                nA=pat.count("*"))


def dense_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward FLOPs per token of everything but the routed experts.

    Mamba-2 layer: in_proj 2·D·(2·di + 2·G·N + H), the depthwise conv
    2·W·(di + 2·G·N), the SSD chunk scan at chunk Q — C·Bᵀ once per
    group (2·Q·N·G) and per head the masked product with X (2·Q·P) and
    the two state products (4·N·P) — and out_proj 2·di·D.  Expert
    layer: the router 2·D·E and the shared expert 4·D·Fs.  Attention
    layer: the projections 2·D·(QH + 2·KV)·hd + 2·QH·hd·D, and QKᵀ and
    PV under the causal mask, on average over the positions of a
    sequence of ``seq`` 2·QH·hd·seq together.  The output head 2·D·V.
    Norms, gates, activations and the loss are left out."""
    k = _n(cfg)
    D, di, G, N, H = k["D"], k["di"], k["G"], k["N"], k["H"]
    mamba = (2 * D * (2 * di + 2 * G * N + H) + 2 * k["W"] * (di + 2 * G * N)
             + 2 * k["Q"] * N * G + H * (2 * k["Q"] * k["P"] + 4 * N * k["P"])
             + 2 * di * D)
    experts = 2 * D * k["E"] + 4 * D * k["Fs"]
    attn = (2 * D * (k["QH"] + 2 * k["KV"]) * k["hd"]
            + 2 * k["QH"] * k["hd"] * D + 2 * k["QH"] * k["hd"] * seq)
    return float(k["nM"] * mamba + k["nE"] * experts + k["nA"] * attn
                 + 2 * D * k["V"])


def window_model_flops(cfg: Dict, seq: int, tokens: int, rows: int
                       ) -> float:
    """Forward + backward model FLOPs (3x the forward) of a window of
    ``tokens`` tokens in which the held experts computed ``rows`` rows
    (each row: 2·D·F in, 2·F·D out)."""
    k = _n(cfg)
    fwd = tokens * dense_flops_per_token(cfg, seq) \
        + rows * 4 * k["D"] * k["F"]
    return 3.0 * fwd


def gmm_need(cfg: Dict, rows: int, layer_steps: int) -> Tuple[float, float]:
    """(flops, bytes) the grouped products of a window need: per expert
    layer and step three pairs of products (forward, and the two
    gradients of the backward pass) over the rows routed to the held
    experts, 2·D·F FLOPs a row each, so 12·D·F a row in all.  Bytes,
    per product, read the rows of its input once and the held experts'
    weights once and write its output once, in bfloat16: six products
    of 2·(rows·(D + F) + held·D·F) per layer and step."""
    k = _n(cfg)
    D, F = k["D"], k["F"]
    flops = 12.0 * D * F * rows
    nbytes = 6 * 2.0 * (rows * (D + F) + layer_steps * k["held"] * D * F)
    return flops, nbytes


def is_gmm_kernel(text: str) -> bool:
    """A grouped-product kernel (Megablox gmm or tgmm): a Mosaic call on
    the group metadata — the tile count s32[], the group offsets, the
    group ids and m-tile ids of each tile, the group offset s32[1] —
    then the rows [M, K] and either the experts' weights [G, K, N]
    (gmm) or a second row operand [M, N] (tgmm)."""
    if not tr.is_tpu_kernel(text):
        return False
    ops = tr.operand_shapes(text)
    if len(ops) != 7 or any(t != "s32" for t, _ in ops[:5]):
        return False
    if [len(d) for _, d in ops[:5]] != [0, 1, 1, 1, 1] or ops[4][1] != (1,):
        return False
    (_, lhs), (_, rhs) = ops[5], ops[6]
    if len(lhs) != 2:
        return False
    return (len(rhs) == 3 and rhs[0] + 1 == ops[1][1][0]) or \
        (len(rhs) == 2 and rhs[0] == lhs[0])


def _expert_layers(cfg: Dict) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def gmm_roofline(ctx) -> Optional[float]:
    """Least time of the grouped products the window's routed rows need
    (``gmm_need``) at the chip's peaks, over the device time of every
    grouped-product kernel in the window, in %."""
    trace, c = ctx["trace"], ctx["counters"]
    if trace is None or not c.get("moe_rows") or ctx["cell"] is None:
        return None
    secs, n = trace.op_seconds(is_gmm_kernel)
    if n == 0 or secs <= 0:
        return None
    cfg = ctx["cell"].config
    flops, nbytes = gmm_need(cfg, c["moe_rows"],
                             c["steps"] * _expert_layers(cfg))
    share, _ = roofline_share(flops, nbytes, secs, ctx["peaks"])
    return share


def train_mfu(ctx) -> Optional[float]:
    """Model FLOPs of the window (``window_model_flops``, the experts at
    their actual rows) per second of the window, over the chips' bf16
    peak, in %."""
    c = ctx["counters"]
    if not c.get("tokens") or not c.get("window_s") or ctx["peaks"] is None \
            or not c.get("moe_rows") or ctx["cell"] is None:
        return None
    cell = ctx["cell"]
    flops = window_model_flops(cell.config, cell.params["seq"],
                               c["tokens"], c["moe_rows"])
    n_chips = max(1, len(ctx["devices"] or ()))
    return 100.0 * flops / c["window_s"] / (n_chips * ctx["peaks"]["bf16_flops"])


def load_imbalance(ctx) -> Optional[float]:
    """The largest held expert's rows over the held experts' mean, per
    expert layer and step, summed over the window: Σ max ÷ (Σ rows ÷
    held)."""
    c = ctx["counters"]
    if not c.get("moe_rows") or ctx["cell"] is None:
        return None
    held = ctx["cell"].config["n_routed_experts"]
    return c["moe_max_rows"] * held / c["moe_rows"]
