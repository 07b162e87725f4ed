"""Plain reference of the log's large-record integrity hash.

A record's hash is the polynomial ``Σ x_i · r^i mod 2^32`` over the
little-endian uint32 lanes of ``(lsn u64, size u32) || payload``, the
last lane zero-padded, with ``r = 2654435761``.  Written from that
definition alone, in NumPy, so that it shares no code with the kernel
or with the program's own oracle.
"""

from __future__ import annotations

import struct

import numpy as np

R = 2654435761
_MASK = 0xFFFFFFFF
_BLOCK = 1 << 12


def _powers(n: int) -> np.ndarray:
    """[r^0 .. r^(n-1)] mod 2^32 as uint64."""
    base = np.empty(_BLOCK, np.uint64)
    acc = 1
    for i in range(_BLOCK):
        base[i] = acc
        acc = (acc * R) & _MASK
    step = acc                                   # r^_BLOCK
    nblk = -(-n // _BLOCK)
    heads = np.empty(nblk, np.uint64)
    acc = 1
    for b in range(nblk):
        heads[b] = acc
        acc = (acc * step) & _MASK
    # (a*b) mod 2^32 without overflow: split b into 16-bit halves
    lo = (heads[:, None] * (base[None, :] & np.uint64(0xFFFF))) \
        & np.uint64(_MASK)
    hi = (heads[:, None] * (base[None, :] >> np.uint64(16))) \
        & np.uint64(0xFFFF)
    out = (lo + (hi << np.uint64(16))) & np.uint64(_MASK)
    return out.reshape(-1)[:n]


class PlainHash:
    def __init__(self, max_bytes: int):
        self.pw = _powers((12 + max_bytes + 3) // 4).astype(np.uint32)

    def __call__(self, lsn: int, payload) -> int:
        size = len(payload)
        raw = np.zeros(((12 + size + 3) // 4) * 4, np.uint8)
        raw[:12] = np.frombuffer(struct.pack("<QI", lsn, size), np.uint8)
        raw[12:12 + size] = np.frombuffer(payload, np.uint8)
        lanes = raw.view("<u4")
        return int(np.sum(lanes * self.pw[:lanes.size], dtype=np.uint32))
