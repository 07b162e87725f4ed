"""Reductions shared by several per-layer metric files."""

from __future__ import annotations

from typing import Optional

from . import trace as tr
from .cost import roofline_share, ssd_forward_cost


def is_hash_kernel(text: str) -> bool:
    """The checksum kernel: a Mosaic call on an [rows, 128] int32 lane
    tile and the [256, 128] weight tile."""
    if not tr.is_tpu_kernel(text):
        return False
    ops = tr.operand_shapes(text)
    return (len(ops) == 2 and ops[0][0] == "s32" and len(ops[0][1]) == 2
            and ops[0][1][1] == 128 and ops[1] == ("s32", (256, 128)))


def is_ssd_kernel(text: str) -> bool:
    """The SSD forward kernel: a Mosaic call on xdt [B,H,nc,Q,P], the
    log-decays as a column and as a row, and B, C [B,G,nc,Q,N]."""
    if not tr.is_tpu_kernel(text):
        return False
    ops = tr.operand_shapes(text)
    if len(ops) != 5 or any(len(d) != 5 for _, d in ops):
        return False
    (_, x), (_, a), (_, ar), (_, b), (_, c) = ops
    return (a[-1] == 1 and ar[-2] == 1 and a[:3] == x[:3]
            and b == c and b[2:4] == x[2:4])


def hash_roofline(ctx) -> Optional[float]:
    """Unpadded bytes the records hashed in the window need, at the
    chip's HBM bandwidth, over the device time of the hash programs
    (the runs that hold the checksum kernel).  The kernel's own time
    leaves out the copy that brings a small operand into VMEM, so it
    alone can read faster than HBM; the whole program cannot."""
    trace = ctx["trace"]
    if trace is None:
        return None
    secs, n = trace.program_seconds(is_hash_kernel)
    nbytes = ctx["counters"].get("hashed_bytes", 0)
    if n == 0 or secs <= 0 or nbytes <= 0:
        return None
    share, _ = roofline_share(0.0, nbytes, secs, ctx["peaks"])
    return share


def device_idle(ctx) -> Optional[float]:
    trace = ctx["trace"]
    if trace is None or trace.window_s <= 0 or not trace.devices:
        return None
    return 100.0 * (1.0 - trace.busy_s() / trace.window_s)


def ssd_roofline(ctx):
    """(share %, bound) of the SSD forward kernel over the window."""
    trace = ctx["trace"]
    if trace is None:
        return None, None
    flops = nbytes = secs = 0.0
    for text, t in trace.ops(is_ssd_kernel):
        f, by = ssd_forward_cost(tr.operand_shapes(text))
        flops += f
        nbytes += by
        secs += t
    if secs <= 0:
        return None, None
    return roofline_share(flops, nbytes, secs, ctx["peaks"])
