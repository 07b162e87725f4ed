"""The replicated large-record WAL shared by the ``wal-large`` cells:
seeded payloads, the replica set as the configuration states it, the
trim rule, and the read-back comparison that decides ``correct``.

The reference is the client's own record of every acknowledged append
(LSN, payload) and the plain hash of ``bench/lib/phash.py``.  The media
walk below reads the on-media record format
(``lsn u64, size u32, hash u32, flags u64`` headers, 8-byte aligned,
PAD records and an implicit skip at the ring's end) written out here
from the format, not taken from the program.
"""

from __future__ import annotations

import struct
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .common import Check, rng_for
from .phash import PlainHash

MIB = 1 << 20
HDR = struct.Struct("<QIIQ")
HDR_SIZE = HDR.size
FLAG_VALID, FLAG_PAD, FLAG_CLEANED, FLAG_PHASH = 1, 2, 4, 8


def align8(n: int) -> int:
    return (n + 7) & ~7


class PayloadPool:
    """One seeded byte pool; record ``k`` of a stream is a slice of it at
    a seeded offset.  The set of sizes is fixed by the configuration
    (``n_sizes`` evenly spaced sizes from ``min`` to ``max``); the seed
    only orders them and picks the offsets, so every seed does the same
    work."""

    def __init__(self, seed: int, rec: Dict, pool_bytes: int):
        self.sizes = np.linspace(rec["min_bytes"], rec["max_bytes"],
                                 rec["n_sizes"]).astype(np.int64)
        self.max = int(self.sizes.max())
        rng = rng_for(seed, 0)
        self.buf = rng.bytes(pool_bytes + self.max)
        self.view = memoryview(self.buf)
        self.span = pool_bytes
        self.seed = seed

    def stream(self, k: int):
        """Endless (size, offset) sequence of stream ``k``: the size
        cycle in a seeded order, offsets 8-byte aligned."""
        rng = rng_for(self.seed, 1, k)
        while True:
            order = rng.permutation(self.sizes.size)
            offs = rng.integers(0, self.span // 8, order.size) * 8
            for i, off in zip(order, offs):
                yield int(self.sizes[i]), int(off)

    def payload(self, size: int, off: int) -> memoryview:
        return self.view[off:off + size]


def build(cfg: Dict, ingest: Optional[Dict] = None):
    """The replica set the configuration states."""
    from repro.core import build_replica_set
    from repro.core.ingest import IngestConfig
    rep = cfg["replication"]
    return build_replica_set(
        mode=rep["mode"], capacity=cfg["ring_bytes"],
        n_backups=rep["n_backups"], write_quorum=rep["write_quorum"],
        ingest=IngestConfig(**ingest) if ingest is not None else None)


def records_in(read, n: int) -> List[int]:
    """LSNs of the whole records in a durable range of ``n`` bytes that
    starts at a record header; ``read(p, k)`` gives ``k`` bytes at ``p``
    from the range's start."""
    out, p = [], 0
    while p + HDR_SIZE <= n:
        lsn, size, _, flags = HDR.unpack(bytes(read(p, HDR_SIZE)))
        if not flags & FLAG_VALID or flags & FLAG_PAD:
            break
        p += align8(HDR_SIZE + size)
        if p > n:
            break
        out.append(lsn)
    return out


class AckTap:
    """When each record became durable on each copy: a tap on the
    primary's flush (``persist``) and on each backup's receipt of a
    replicated write (``handle_write_imm``, which stores and persists).
    Each durable ring range is stamped on the host clock once the call
    returns, with the LSNs of the whole records it holds, read from the
    range itself.  An ack taken on the client's clock after the call
    that made it durable can only come later, so a record acked before
    its copies hold it shows as a stamp after its ack."""

    def __init__(self, rs):
        from repro.core.log import ring_offset
        self.roff = ring_offset()
        self.primary: Dict[int, float] = {}
        self.backups: List[Dict[int, float]] = []
        dev = rs.primary_dev
        persist = dev.persist

        def flushed(off, n):
            vns = persist(off, n)
            if off >= self.roff:
                self._stamp(self.primary, lambda p, k: dev.read(off + p, k),
                            n)
            return vns

        dev.persist = flushed
        for srv in rs.servers:
            landed: Dict[int, float] = {}
            self.backups.append(landed)
            handle = srv.handle_write_imm

            def received(dst_off, data, primary_id, handle=handle,
                         landed=landed):
                vns = handle(dst_off, data, primary_id)
                if dst_off >= self.roff:
                    mv = memoryview(data)
                    self._stamp(landed, lambda p, k: mv[p:p + k], len(mv))
                return vns

            srv.handle_write_imm = received

    @staticmethod
    def _stamp(into: Dict[int, float], read, n: int) -> None:
        t = time.perf_counter()
        for lsn in records_in(read, n):
            into.setdefault(lsn, t)

    def acked_early(self, acks: Dict[int, float], quorum: int) -> int:
        """Acks taken before the primary and ``quorum - 1`` backups held
        the record."""
        late = float("inf")
        bad = 0
        for lsn, t in acks.items():
            copies = sum(1 for b in self.backups if b.get(lsn, late) <= t)
            if self.primary.get(lsn, late) > t or 1 + copies < quorum:
                bad += 1
        return bad


class Acked:
    """The client's record of acknowledged appends, and the trim rule:
    when the ring's free share falls to ``low_frac``, trim every durable
    record except the newest ``keep_frac`` of the ring."""

    def __init__(self, ring: int, trim: Optional[Dict]):
        self.ring = ring
        self.trim_cfg = trim
        self.lock = threading.Lock()
        self.recs: Dict[int, Tuple[int, int]] = {}     # lsn -> size, off
        self.times: Dict[int, float] = {}              # lsn -> ack time
        self.trimmed_upto = 0

    def add(self, lsn: int, size: int, off: int, t_ack: float) -> None:
        with self.lock:
            self.recs[lsn] = (size, off)
            self.times[lsn] = t_ack

    def attach(self, log) -> None:
        if self.trim_cfg is None:
            return
        log.cfg.free_space_low_frac = self.trim_cfg["low_frac"]
        log.on_free_space_low = self._reclaim

    def _reclaim(self, log) -> None:
        keep = self.trim_cfg["keep_frac"] * self.ring
        durable = log.durable_lsn
        with self.lock:
            lsns = sorted((l for l in self.recs
                           if self.trimmed_upto < l <= durable),
                          reverse=True)
            live, upto = 0, None
            for l in lsns:
                live += align8(HDR_SIZE + self.recs[l][0])
                if live > keep:
                    upto = l
                    break
        if upto is not None:
            log.trim(upto)
            with self.lock:
                self.trimmed_upto = max(self.trimmed_upto, upto)
                for l in [l for l in self.recs if l <= upto]:
                    del self.recs[l]

    def live(self) -> Dict[int, Tuple[int, int]]:
        """Acked records above the trim (an ack can be recorded after a
        trim that already covers it)."""
        with self.lock:
            return {l: v for l, v in self.recs.items()
                    if l > self.trimmed_upto}


def walk(dev, ring_off: int, cap: int, head_off: int, first_lsn: int,
         last_lsn: int) -> Dict[int, Tuple[int, int, int, int]]:
    """lsn -> (ring pos, size, hash, flags) along the header chain."""
    out: Dict[int, Tuple[int, int, int, int]] = {}
    pos, lsn = head_off, first_lsn
    while lsn <= last_lsn:
        if cap - pos < HDR_SIZE:
            pos = 0
            continue
        got, size, crc, flags = HDR.unpack(dev.read(ring_off + pos,
                                                    HDR_SIZE))
        if got != lsn or not flags & FLAG_VALID:
            break
        out[lsn] = (pos, size, crc, flags)
        if flags & FLAG_PAD:
            pos = 0
        else:
            pos += align8(HDR_SIZE + size)
            if pos >= cap:
                pos = 0
        lsn += 1
    return out


def payload_byte(dev, cap: int, lsn: int, size: int, seed: int) -> int:
    """Media offset of one payload byte of record ``lsn`` (``size``
    bytes), drawn from the seed, in a ring of ``cap`` bytes filled from
    its start and never trimmed."""
    from repro.core.log import ring_offset
    roff = ring_offset()
    first = HDR.unpack(dev.read(roff, HDR_SIZE))[0]
    pos = walk(dev, roff, cap, 0, first, lsn)[lsn][0]
    return roff + pos + HDR_SIZE + int(rng_for(seed, 9).integers(0, size))


def compare(rs, acked: Acked, pool: PayloadPool, window_acks: int,
            tap: AckTap, quorum: int, opened=None) -> List[Check]:
    """Every acknowledged record was held by the primary and by
    ``quorum - 1`` backups when its ack came; every one still live reads
    back byte-equal after a crash of the primary (through the recovery
    path: ``opened``, an ``(image, Log)`` pair the window recovered, or
    a fresh crash image and ``Log.open``) and from each backup's media;
    every record's header carries the plain hash."""
    from repro.core import Log, LogConfig
    from repro.core.log import ring_offset
    expected = acked.live()
    cap = rs.cfg.capacity
    roff = ring_offset()
    if opened is None:
        img = rs.primary_dev.crash()
        relog = Log.open(img, LogConfig(capacity=cap))
    else:
        img, relog = opened
    got = dict(relog.iter_records())
    missing = sum(1 for l, (s, o) in expected.items()
                  if got.get(l) != bytes(pool.payload(s, o)))
    missing += sum(1 for l in got if l not in expected)
    head = relog.read_superline()
    hi = max(expected) if expected else 0
    chain = walk(img, roff, cap, head.head_off, head.head_lsn, hi)
    ph = PlainHash(pool.max)
    hash_bad = 0
    for l, (s, o) in expected.items():
        h = chain.get(l)
        if h is None or not h[3] & FLAG_PHASH or \
                h[2] != ph(l, pool.payload(s, o)):
            hash_bad += 1
    backup_bad = 0
    for srv in rs.servers:
        bdev = srv.device.crash()
        for l, (s, o) in expected.items():
            h = chain.get(l)
            if h is None:
                backup_bad += 1
                continue
            raw = bdev.read(roff + h[0], HDR_SIZE + s)
            if HDR.unpack_from(raw) != (l, s, h[2], h[3]) or \
                    raw[HDR_SIZE:] != bytes(pool.payload(s, o)):
                backup_bad += 1
    del img, relog, got
    return [Check("acked_before_quorum",
                  tap.acked_early(acked.times, quorum), 0),
            Check("records_missing_or_differ", missing, 0),
            Check("hash_not_plain", hash_bad, 0),
            Check("backup_records_differ", backup_bad, 0),
            Check("no_ack_in_window", int(window_acks == 0), 0)]
