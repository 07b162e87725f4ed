"""Closed-loop producers over the group-commit ingest engine.

Each of ``producers`` threads appends a record through
``IngestEngine.append`` and waits for its durable ack before it sends
the next.  Latency is taken on the client side, from the call of
``append`` to the return of ``ticket.wait``; the ack's time is kept,
so that the comparison can tell whether the record's copies held it
then (``wal.AckTap``).
"""

from __future__ import annotations

import threading
import time
from typing import List

from bench.lib import wal
from bench.lib.common import Cell, SpanLog, WindowResult, nearest_rank
from bench.lib.cost import hash_bytes


class Driver:
    def __init__(self, cell: Cell, spans: SpanLog):
        self.cell = cell
        self.spans = spans
        self.p = cell.params
        self.cfg = cell.config

    def setup(self) -> None:
        cfg, p = self.cfg, self.p
        self.pool = wal.PayloadPool(self.cell.seed, cfg["records"],
                                    p["pool_bytes"])
        self.rs = wal.build(cfg, ingest=p["ingest"])
        self.tap = wal.AckTap(self.rs)
        self.engine = self.rs.ingest
        self.acked = wal.Acked(cfg["ring_bytes"], cfg["trim"])
        self.acked.attach(self.rs.log)
        self.streams = [self.pool.stream(k) for k in range(p["producers"])]
        # fill the ring to the trim rule's steady level through the
        # batched append path, then one record of every padded hash
        # shape through the engine, so nothing compiles in the window
        fill = self.pool.stream(p["producers"])
        todo, batch = p["prefill_bytes"], []
        while todo > 0:
            size, off = next(fill)
            batch.append((size, off))
            todo -= size
            if len(batch) == 8 or todo <= 0:
                lsns = self.rs.log.append_batch(
                    [self.pool.payload(s, o) for s, o in batch], freq=1)
                t = time.perf_counter()
                for l, (s, o) in zip(lsns, batch):
                    self.acked.add(l, s, o, t)
                batch = []
        for size in sorted({int(self.pool.sizes.min()),
                            int(self.pool.sizes.max()),
                            int(self.pool.sizes[len(self.pool.sizes) // 2])}):
            t = self.engine.append(self.pool.payload(size, 0))
            self.acked.add(t.wait(timeout=120), size, 0,
                           time.perf_counter())
        self.engine.drain()

    def _producer(self, k: int, start: threading.Barrier, out: List) -> None:
        stream, eng, span = self.streams[k], self.engine, self.spans.span
        start.wait()
        end = self.t_end
        while time.perf_counter() < end:
            size, off = next(stream)
            data = self.pool.payload(size, off)
            t0 = time.perf_counter()
            try:
                with span("bench.append"):
                    ticket = eng.append(data)
                with span("bench.wait_ack"):
                    lsn = ticket.wait(timeout=120)
            except Exception as exc:          # counted as failed
                out.append((None, size, off, t0, time.perf_counter(),
                            repr(exc)))
                continue
            t1 = time.perf_counter()
            self.acked.add(lsn, size, off, t1)
            out.append((lsn, size, off, t0, t1, None))

    def window(self, seconds: float) -> WindowResult:
        n = self.p["producers"]
        start = threading.Barrier(n + 1)
        outs = [[] for _ in range(n)]
        threads = [threading.Thread(target=self._producer,
                                    args=(k, start, outs[k]),
                                    name=f"bench-producer-{k}")
                   for k in range(n)]
        for t in threads:
            t.start()
        s0 = self.engine.stats()
        self.t_start = time.perf_counter()
        self.t_end = self.t_start + seconds
        start.wait()
        for t in threads:
            t.join()
        s1 = self.engine.stats()
        recs = [r for o in outs for r in o]
        done = [r for r in recs if r[0] is not None and r[4] <= self.t_end]
        self.window_acks = len(done)
        lat = [(r[4] - r[3]) * 1e3 for r in done]
        e2e = {"ack_p95_ms": nearest_rank(lat, 95.0),
               "durable_MBps": sum(r[1] for r in done) / seconds / 1e6}
        counters = {
            "acked": s1["acked"] - s0["acked"],
            "waves": s1["waves"] - s0["waves"],
            "hashed_bytes": hash_bytes([r[1] for r in recs]),
            "records": len(recs), "ack_p50_ms": nearest_rank(lat, 50.0),
            "window_acks": len(done),
        }
        return WindowResult(e2e, attempted=len(recs),
                            failed=sum(1 for r in recs if r[0] is None),
                            counters=counters)

    def free(self) -> None:
        self.engine.drain()
        self.rs.group.drain()

    def check(self):
        return wal.compare(self.rs, self.acked, self.pool, self.window_acks,
                           self.tap, self.cfg["replication"]["write_quorum"])

    def close(self) -> None:
        self.rs.shutdown()
