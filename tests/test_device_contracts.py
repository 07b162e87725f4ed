"""Device contracts: the exact hardware-event counts and state identities
the paper's flush-economy and pipelining claims rest on.

Each case is a small pinned workload.  What it pins:

* append paths (fig5) — ``DeviceStats`` of N scalar sync appends and of
  the same records in batches of 16 and 128, on both device models,
  exactly;
* pipelined force (fig6) — ``DeviceStats`` on every copy and the durable
  and recovered record set identical at pipeline depth 1, 2, 4 and under
  the adaptive controller, over an injected wire delay; and a mid-wire
  backup death salvaged to the no-fault content with only the missing
  lane's bytes re-sent;
* recovery (fig7) — the vectorized scan's state equal to the sequential
  chain walk, the trimmed lifecycle recovering exactly the live suffix,
  and online resync repairing a fraction of the image;
* force policies (fig8) — every record durable after drain and the
  window within the policy's vulnerability bound;
* ingestion (fig9) — the grouped front end and the sharded router
  recover the same record multiset as a serial run.

No case reads a clock: only counts, bytes and digests.
"""

from __future__ import annotations

import threading
import zlib
from collections import deque

import pytest

from repro.apps.kvstore import DurableKV, encode_put
from repro.core import (FreqPolicy, IngestConfig, Log, LogConfig,
                        LogFullError, PMEMDevice, build_replica_set,
                        make_policy)
from repro.core.force_policy import SyncPolicy
from repro.core.log import ring_offset
from repro.core.replication import device_size
from repro.core.router import LogRouter, ShardSpec

STAT_KEYS = ("writes", "bytes_written", "flushes", "lines_flushed", "fences")
LLC_KEYS = STAT_KEYS + ("llc_misses", "llc_hits")


def _stats(dev, keys=STAT_KEYS) -> dict:
    return {k: getattr(dev.stats, k) for k in keys}


def _nic_stats(dev) -> dict:
    """Stats plus the cache lines the NIC read from this device.  The
    hit/miss split is not pinned: ``Log.create`` replicates its metadata
    synchronously, and the W-th ack can return before the straggler
    lane's NIC read, which may then land before or after the local flush
    that evicts those lines."""
    out = _stats(dev)
    out["nic_lines"] = dev.stats.llc_hits + dev.stats.llc_misses
    return out


def _digest(log: Log) -> tuple:
    """(crc over every (lsn, payload) in LSN order, record count)."""
    digest, n = 0, 0
    for lsn, p in log.iter_records():
        digest = zlib.crc32(p, zlib.crc32(str(lsn).encode(), digest))
        n += 1
    return digest, n


# ---------------------------------------------------------------------- #
# fig5: append paths
# ---------------------------------------------------------------------- #
CAP5 = 1 << 22
N5 = 2000
SIZE5 = 64

# Log.create seeds the superline copies and the trim slot; then every
# scalar sync append is one header+payload store (two in the strict
# model, which cannot hand out a direct pointer), one flush and one fence.
SCALAR_STATS = {
    "fast": {"writes": 2003, "bytes_written": 48060, "flushes": 2002,
             "lines_flushed": 2502, "fences": 2002},
    "strict": {"writes": 4003, "bytes_written": 176060, "flushes": 2002,
               "lines_flushed": 4502, "fences": 2002},
}
# a batch is one store of every header and payload and one flush+fence
BATCH_STATS = {
    16: {"writes": 128, "bytes_written": 176060, "flushes": 127,
         "lines_flushed": 2752, "fences": 127},
    128: {"writes": 18, "bytes_written": 169020, "flushes": 17,
          "lines_flushed": 2642, "fences": 17},
}


@pytest.mark.parametrize("mode", ["fast", "strict"])
def test_scalar_sync_append_stats(mode):
    dev = PMEMDevice(device_size(CAP5), mode=mode)
    log = Log.create(dev, LogConfig(capacity=CAP5))
    payload = b"x" * SIZE5
    for _ in range(N5):
        log.append(payload)
    assert log.durable_lsn == N5
    assert _stats(dev) == SCALAR_STATS[mode]


@pytest.mark.parametrize("mode", ["fast", "strict"])
@pytest.mark.parametrize("bs", [16, 128])
def test_batch_append_stats(mode, bs):
    dev = PMEMDevice(device_size(CAP5), mode=mode)
    log = Log.create(dev, LogConfig(capacity=CAP5))
    for _ in range(N5 // bs):
        log.append_batch([b"x" * SIZE5] * bs)
    assert log.durable_lsn == (N5 // bs) * bs
    assert _stats(dev) == BATCH_STATS[bs]


# ---------------------------------------------------------------------- #
# fig6: pipelined force over a delayed wire, and salvage
# ---------------------------------------------------------------------- #
CAP6 = 1 << 22
PIPE_DELAY_S = 0.004          # injected wire delay per durability round
PIPE_RECORDS = 96
PIPE_WARM = 8
PIPE_FREQ = 4                 # force leader every 4th LSN
PIPE_PAYLOAD = 1024

# identical at every depth: overlap moves wall time, never device work
PIPE_STATS = {
    "node0": {"writes": 107, "bytes_written": 2556, "flushes": 34,
              "lines_flushed": 132, "fences": 34, "nic_lines": 3448},
    "node1": {"writes": 34, "bytes_written": 109052, "flushes": 34,
              "lines_flushed": 1724, "fences": 34, "nic_lines": 0},
    "node2": {"writes": 34, "bytes_written": 109052, "flushes": 34,
              "lines_flushed": 1724, "fences": 34, "nic_lines": 0},
}
PIPE_DIGEST = 782714043


@pytest.mark.parametrize("depth,adaptive",
                         [(1, False), (2, False), (4, False), (8, True)],
                         ids=["depth1", "depth2", "depth4", "adaptive"])
def test_pipeline_depth_keeps_device_work_and_content(depth, adaptive):
    rs = build_replica_set(mode="local+remote", capacity=CAP6, n_backups=2,
                           write_quorum=2, pipeline_depth=depth,
                           adaptive_depth=adaptive)
    try:
        payload = b"p" * PIPE_PAYLOAD
        pol = FreqPolicy(PIPE_FREQ, wait=False)
        for _ in range(PIPE_WARM):
            rs.log.append(payload)
        rs.log.drain()
        for t in rs.transports:
            t.inject(delay_s=PIPE_DELAY_S)
        for _ in range(PIPE_RECORDS):
            rid, ptr = rs.log.reserve(len(payload))
            ptr[:] = payload
            rs.log.complete(rid)
            pol.on_complete(rs.log, rid)
        pol.drain(rs.log)
        rs.group.drain()
        total = PIPE_WARM + PIPE_RECORDS
        assert rs.log.durable_lsn == total
        if adaptive:
            depths = [d for _, d in rs.log.depth_trajectory]
            assert depths and max(depths) <= depth
        stats = {name: _nic_stats(dev)
                 for name, dev in sorted(rs.server_devices().items())}
        assert stats == PIPE_STATS
        relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP6))
        assert _digest(relog) == (PIPE_DIGEST, total)
    finally:
        rs.shutdown()


SALV_RECORDS = 48
SALV_FAIL_AT = 24
SALV_SLOW_S = 0.03            # dying backup: acks never land in time
SALV_FAST_S = 0.002           # healthy backup: acks land first
SALV_DIGEST = 82838224
SALV_PRIMARY_STATS = {"writes": 59, "bytes_written": 1404, "flushes": 22,
                      "lines_flushed": 72, "fences": 22}
# W=3 over local + 2 backups: only the dead backup's lane misses the
# failed rounds, so salvage re-sends at most half their wire bytes
SALV_REISSUE_FRACTION = 0.5


def _salvage_run(fault: bool) -> dict:
    rs = build_replica_set(mode="local+remote", capacity=CAP6, n_backups=2,
                           write_quorum=3, pipeline_depth=4)
    try:
        log = rs.log
        pol = FreqPolicy(PIPE_FREQ, wait=False)
        payload = b"s" * PIPE_PAYLOAD
        for _ in range(PIPE_WARM):
            log.append(payload)
        log.drain()
        rs.transports[0].inject(delay_s=SALV_SLOW_S)
        rs.transports[1].inject(delay_s=SALV_FAST_S)
        for i in range(SALV_RECORDS):
            if fault and i == SALV_FAIL_AT:
                rs.kill_backup_midwire("node1", settle_s=SALV_FAST_S * 8)
                rs.recover_backup("node1")
            rid, ptr = log.reserve(len(payload))
            ptr[:] = payload
            log.complete(rid)
            pol.on_complete(log, rid)
        pol.drain(log)
        rs.group.drain()
        st = log.stats()
        relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP6))
        return dict(content=_digest(relog), durable=st["durable_lsn"],
                    salvage_rounds=st["salvage_rounds"],
                    reissue_bytes=st["reissue_bytes"],
                    failed_bytes=st["full_reissue_bytes"],
                    stats=_stats(rs.primary_dev))
    finally:
        rs.shutdown()


def test_salvage_resends_only_missing_lane_bytes():
    control = _salvage_run(False)
    fault = _salvage_run(True)
    total = PIPE_WARM + SALV_RECORDS
    assert control["content"] == fault["content"] == (SALV_DIGEST, total)
    assert control["durable"] == fault["durable"] == total
    assert control["stats"] == fault["stats"] == SALV_PRIMARY_STATS
    assert control["salvage_rounds"] == 0
    assert fault["salvage_rounds"] >= 1
    assert 0 < fault["reissue_bytes"] \
        <= SALV_REISSUE_FRACTION * fault["failed_bytes"]


# ---------------------------------------------------------------------- #
# fig7: recovery
# ---------------------------------------------------------------------- #
CAP7 = 1 << 18
REC7 = 1024
PHASH_T = 256
# (records, DeviceStats) of filling the ring: 64-record batches, then
# scalar appends until the ring is full
FILL7_STATS = {"writes": 64, "bytes_written": 202668, "flushes": 63,
               "lines_flushed": 3218, "fences": 63}
FILL7 = {"crc32": (250, FILL7_STATS), "phash": (250, FILL7_STATS)}


def _fill_ring(cfg: LogConfig):
    dev = PMEMDevice(device_size(cfg.capacity), mode="fast")
    log = Log.create(dev, cfg)
    payload = b"r" * REC7
    n = 0
    try:
        while True:
            log.append_batch([payload] * 64)
            n += 64
    except LogFullError:
        pass
    try:
        while True:
            log.append(payload)
            n += 1
    except LogFullError:
        pass
    return dev, n


@pytest.mark.parametrize("integrity", ["crc32", "phash"])
def test_vectorized_scan_equals_sequential_walk(integrity):
    cfg = LogConfig(capacity=CAP7, phash_threshold=(
        PHASH_T if integrity == "phash" else None))
    dev, n_filled = _fill_ring(cfg)
    assert (n_filled, _stats(dev)) == FILL7[integrity]
    before = _stats(dev, LLC_KEYS)
    relog = Log.open(dev, cfg)
    raw = relog._ring_snapshot()
    s = relog.read_superline()
    plan, _ = relog._walk_chain(raw, s.head_off, s.head_lsn, 0)
    assert (plan.next_lsn, plan.tail, plan.used) == \
        (relog._next_lsn, relog._tail_off, relog._used)
    assert relog._next_lsn - relog._head_lsn == n_filled
    assert sum(1 for _ in relog.iter_records()) == n_filled
    # recovery reads; it never writes, flushes or touches the LLC model
    assert _stats(dev, LLC_KEYS) == before


LIFE_CAP = 1 << 18
LIFE_REC = 1024
LIFE_KEEP = 64                # records each trim keeps live
LIFE_TRIM_FRAC = 0.5          # trim when the ring crosses half full
# a history of the records an untrimmed ring of age x LIFE_CAP holds:
# age -> (records appended, trims, live records recovered)
LIFE = {4: (1000, 15, 73), 16: (4002, 63, 111)}


def _life_payload(n: int) -> bytes:
    return bytes([(n * 37 + 11) & 0xFF]) * LIFE_REC


@pytest.mark.parametrize("age", sorted(LIFE))
def test_trimmed_lifecycle_recovers_exactly_the_live_suffix(age):
    n, want_trims, want_tail = LIFE[age]
    cfg = LogConfig(capacity=LIFE_CAP)
    dev = PMEMDevice(device_size(LIFE_CAP), mode="fast")
    log = Log.create(dev, cfg)
    trims = 0
    expect = {}
    for i in range(n):
        p = _life_payload(i + 1)
        expect[log.append(p)] = p
        if log.stats()["used"] > LIFE_TRIM_FRAC * cfg.capacity:
            log.trim(log.durable_lsn - LIFE_KEEP)
            trims += 1
    relog = Log.open(dev, cfg)
    got = dict(relog.iter_records())
    head = relog._head_lsn
    assert (trims, len(got)) == (want_trims, want_tail)
    assert sorted(got) == sorted(l for l in expect if l >= head)
    assert all(got[l] == expect[l] for l in got)
    assert relog.read_trim_watermark() == log.trim_lsn
    assert head == log.trim_lsn + 1 and min(got) == head


RESYNC_CAP = 1 << 20
RESYNC_REC = 1024
RESYNC_BASE = 96              # records replicated before the backup dies
RESYNC_GAP = 96               # records the dead backup misses
RESYNC_REPAIR_CEIL = 0.5      # repair traffic under half the image


def test_online_resync_repairs_a_fraction_of_the_image():
    rs = build_replica_set(mode="local+remote", capacity=RESYNC_CAP,
                           n_backups=2, write_quorum=2, pipeline_depth=4)
    try:
        payload = b"y" * RESYNC_REC
        for _ in range(RESYNC_BASE):
            rs.log.append(payload)
        rs.kill_backup_midwire("node1")
        for _ in range(RESYNC_GAP):
            rs.log.append(payload)
        rep = rs.recover_backup("node1")
        rs.log.drain()
        rs.group.drain()
        full_image = ring_offset() + rs.cfg.capacity
        node1 = next(s for s in rs.servers if s.server_id == "node1")
        assert node1.device.read(0, full_image) == \
            rs.primary_dev.read(0, full_image)
        assert rep.repair_bytes == rep.catchup_bytes + rep.cutover_bytes
        assert 0 < rep.repair_bytes < RESYNC_REPAIR_CEIL * full_image
    finally:
        rs.shutdown()


# ---------------------------------------------------------------------- #
# fig8: force policies across threads
# ---------------------------------------------------------------------- #
CAP8 = 1 << 22
REC8 = 256
N8 = 1600
FIG8_POLICIES = {"sync": ("sync", {}), "group64": ("group",
                                                   {"group_size": 64}),
                 "freq8": ("freq", {"freq": 8})}


@pytest.mark.parametrize("threads", [1, 8])
@pytest.mark.parametrize("policy", sorted(FIG8_POLICIES))
def test_policy_drains_durable_within_bound(policy, threads):
    name, kw = FIG8_POLICIES[policy]
    dev = PMEMDevice(device_size(CAP8))
    log = Log.create(dev, LogConfig(capacity=CAP8, max_threads=threads))
    pol = make_policy(name, **kw)
    payload = b"f" * REC8
    per = N8 // threads

    def worker() -> None:
        for _ in range(per):
            rid, ptr = log.reserve(len(payload))
            ptr[:] = payload
            log.complete(rid)
            pol.on_complete(log, rid)

    ths = [threading.Thread(target=worker) for _ in range(threads)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    assert log.vulnerability_window() <= pol.vulnerability_bound(log)
    pol.drain(log)
    assert log.durable_lsn == N8
    assert log.vulnerability_window() == 0


# ---------------------------------------------------------------------- #
# fig9: ingestion front end and shards against a serial reference
# ---------------------------------------------------------------------- #
ING_CAP = 1 << 22
ING_THREADS = 16
ING_OPS = 100
ING_WINDOW = 16
ING_DEPTH = 4
ING_VAL = b"v" * 100
SHARD_CAP = 1 << 20
SHARD_WINDOW = 32
# crc over the sorted payloads of every put: the serial reference
ING_DIGEST = 1148818206


def _ing_keys():
    return [[f"k{t:02d}-{i:04d}".encode() for i in range(ING_OPS)]
            for t in range(ING_THREADS)]


def _sorted_digest(payloads) -> int:
    digest = 0
    for p in sorted(payloads):
        digest = zlib.crc32(p, digest)
    return digest


def _check_recovered(lsn_payloads) -> None:
    lsns = [l for l, _ in lsn_payloads]
    assert lsns == list(range(1, ING_THREADS * ING_OPS + 1))
    assert _sorted_digest(bytes(p) for _, p in lsn_payloads) == ING_DIGEST


def _ingest_run(grouped: bool) -> list:
    n_threads = ING_THREADS if grouped else 1
    rs = build_replica_set(mode="local+remote", capacity=ING_CAP,
                           n_backups=1, device_mode="strict",
                           pipeline_depth=ING_DEPTH if grouped else 1)
    kv = DurableKV(rs.log, SyncPolicy(),
                   ingest=IngestConfig() if grouped else None)
    keys = _ing_keys()

    def producer(tid: int) -> None:
        if grouped:
            pend: deque = deque()
            for k in keys[tid]:
                pend.append(kv.put_async(k, ING_VAL))
                if len(pend) >= ING_WINDOW:
                    pend.popleft().wait()
            while pend:
                pend.popleft().wait()
        else:
            for k in (k for ks in keys for k in ks):
                kv.put(k, ING_VAL)

    ths = [threading.Thread(target=producer, args=(t,))
           for t in range(n_threads)]
    for th in ths:
        th.start()
    for th in ths:
        th.join()
    kv.flush()
    if grouped:
        eng = kv.ingest.stats()
        assert eng["submitted"] == eng["acked"] == ING_THREADS * ING_OPS
        assert eng["failed"] == 0
    kv.close()
    rs.shutdown()
    relog = Log.open(rs.primary_dev, LogConfig(capacity=ING_CAP))
    return list(relog.iter_records())


@pytest.mark.parametrize("shape", ["serial", "grouped"])
def test_ingest_recovers_serial_reference(shape):
    _check_recovered(_ingest_run(shape == "grouped"))


@pytest.mark.parametrize("n_shards", [1, 2, 4, 8])
def test_sharded_ingest_recovers_serial_reference(n_shards):
    router = LogRouter()
    for i in range(n_shards):
        router.add_shard(ShardSpec(
            shard_id=f"s{i}", mode="local+remote", capacity=SHARD_CAP,
            n_backups=1, device_mode="strict", pipeline_depth=ING_DEPTH,
            ingest=IngestConfig()))
    keys = _ing_keys()

    def producer(tid: int) -> None:
        pend: deque = deque()
        for k in keys[tid]:
            pend.append(router.submit(encode_put(k, ING_VAL), key=k)[1])
            if len(pend) >= SHARD_WINDOW:
                pend.popleft().wait()
        while pend:
            pend.popleft().wait()

    ths = [threading.Thread(target=producer, args=(t,))
           for t in range(ING_THREADS)]
    try:
        for th in ths:
            th.start()
        for th in ths:
            th.join()
        router.drain()
        payloads = []
        for sid in router.shard_ids:
            sh = router.shard(sid)
            lsns = []
            for lsn, p in sh.log.iter_records():
                lsns.append(lsn)
                payloads.append(bytes(p))
            assert lsns == list(range(1, len(lsns) + 1))
            eng = sh.engine.stats()
            assert eng["failed"] == 0 and eng["acked"] == len(lsns)
        assert len(payloads) == ING_THREADS * ING_OPS
        assert _sorted_digest(payloads) == ING_DIGEST
    finally:
        router.shutdown()
