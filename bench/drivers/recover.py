"""Crash recovery of a filled replicated ring, back to back.

Set-up acknowledges ``fill_cycles`` full cycles of the configuration's
record sizes (the same records for every seed, in a seeded order)
through the batched append path, then one record more whose payload
has one byte flipped on the primary's media, drains replication and
keeps the primary's pre-crash image.  The window calls ``Log.open`` on
a fresh crash image of it again and again; each open scans the chain,
validates every record's payload hash in one batched kernel call and
has to truncate the chain at the corrupt record.
"""

from __future__ import annotations

import time

from bench.lib import wal
from bench.lib.common import Cell, Check, SpanLog, WindowResult
from bench.lib.cost import hash_bytes


class Driver:
    def __init__(self, cell: Cell, spans: SpanLog):
        self.cell = cell
        self.spans = spans
        self.p = cell.params
        self.cfg = cell.config

    def setup(self) -> None:
        from repro.core import Log
        cfg, p = self.cfg, self.p
        self.pool = wal.PayloadPool(self.cell.seed, cfg["records"],
                                    p["pool_bytes"])
        self.rs = wal.build(cfg)
        self.tap = wal.AckTap(self.rs)
        self.acked = wal.Acked(cfg["ring_bytes"], None)
        stream = self.pool.stream(0)
        recs = [next(stream)
                for _ in range(p["fill_cycles"] * self.pool.sizes.size)]
        for i in range(0, len(recs), p["append_batch"]):
            part = recs[i:i + p["append_batch"]]
            lsns = self.rs.log.append_batch(
                [self.pool.payload(s, o) for s, o in part], freq=1)
            t = time.perf_counter()
            for l, (s, o) in zip(lsns, part):
                self.acked.add(l, s, o, t)
        self.last_lsn = max(self.acked.recs)
        # the corrupt record: appended, then one payload byte flipped on
        # the primary's media only; the reference does not expect it
        size, off = next(stream)
        bad = self.rs.log.append_batch([self.pool.payload(size, off)],
                                       freq=1)[0]
        self.rs.group.drain()
        dev = self.rs.primary_dev
        at = wal.payload_byte(dev, cfg["ring_bytes"], bad, size,
                              self.cell.seed)
        dev.write(at, bytes([dev.read(at, 1)[0] ^ 0xFF]))
        self.sizes = [s for s, _ in recs] + [size]
        self.image = dev
        # one open compiles the recovery scan's hash shape
        Log.open(self.image.crash(), self.rs.cfg, repl=self.rs.group)

    def window(self, seconds: float) -> WindowResult:
        from repro.core import Log
        span = self.spans.span
        opens, self.wrong_tail = [], 0
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            self.opened = None
            with span("bench.crash_image"):
                img = self.image.crash()
            t0 = time.perf_counter()
            with span("bench.log_open"):
                log = Log.open(img, self.rs.cfg, repl=self.rs.group)
            opens.append(time.perf_counter() - t0)
            self.wrong_tail += int(log.durable_lsn != self.last_lsn)
            # the last open's log is the one read back
            self.opened = (img, log)
            del img, log
        return WindowResult(
            {"recover_s": sum(opens) / len(opens)},
            attempted=len(opens), failed=0,
            counters={"opens": len(opens),
                      "hashed_bytes": len(opens) * hash_bytes(self.sizes),
                      "records": len(self.sizes),
                      "window_acks": len(opens)})

    def free(self) -> None:
        pass

    def check(self):
        checks = wal.compare(self.rs, self.acked, self.pool,
                             len(self.sizes), self.tap,
                             self.cfg["replication"]["write_quorum"],
                             opened=self.opened)
        self.opened = None
        return checks + [Check("opens_ending_elsewhere", self.wrong_tail,
                               0)]

    def close(self) -> None:
        self.rs.shutdown()
