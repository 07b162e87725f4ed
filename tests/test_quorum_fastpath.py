"""W-th-ack replication fast path: early quorum return, straggler
harvest + eviction, doorbell-batched segment replication, parallel
broadcast (PR 2, §4.2 Replication)."""

import time

import pytest

from repro.core import (Log, LogConfig, PMEMDevice, QuorumError,
                        build_replica_set, write_and_force_segs)
from repro.core.log import ring_offset

pytestmark = pytest.mark.slow   # spins up replica servers per test

CAP = 1 << 16
DELAY = 0.25


def test_replicate_returns_at_wth_ack_not_slowest():
    """W < N: one delayed backup must not bound replicate wall-clock."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)          # local + 1 remote ack
    rs.transports[1].inject(delay_s=DELAY)          # node2 is a straggler
    t0 = time.perf_counter()
    rs.log.append(b"fast-quorum")
    dt = time.perf_counter() - t0
    assert dt < DELAY, f"append took {dt:.3f}s: bounded by the straggler"
    assert rs.log.durable_lsn == 1
    # the straggler still completes in the background: after drain both
    # backups hold identical ring bytes (no gap, just lag)
    rs.group.drain()
    ring = rs.primary_dev.read(0, ring_offset() + CAP)
    for s in rs.servers:
        assert s.device.read(0, len(ring))[ring_offset():] == \
            ring[ring_offset():]
    rs.shutdown()


def test_late_transport_error_evicts_before_next_replicate():
    """A straggler that fails after the quorum returned is evicted by the
    background harvest — at the latest before its lane runs another op —
    so no half-attached backup can linger (§4.2)."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    t = rs.transports[1]
    t.inject(delay_s=0.05, drop=True)               # late failure
    rs.log.append(b"a")                             # quorum met without node2
    assert rs.log.durable_lsn == 1
    rs.group.drain()                                # harvest the late failure
    assert t.closed                                 # evicted
    rs.log.append(b"b")                             # quorum still met (W=2)
    assert rs.log.durable_lsn == 2
    # node2 observed a prefix (nothing), never a gap
    relog = Log.open(rs.servers[0].device, LogConfig(capacity=CAP))
    assert [p for _, p in relog.iter_records()] == [b"a", b"b"]
    rs.shutdown()


def test_straggler_lane_is_fifo_no_gap():
    """Writes queued behind a slow backup apply in order once it catches
    up: the backup may lag, but its ring is always a prefix-consistent
    image."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=1)          # local ack alone meets W
    rs.transports[0].inject(delay_s=0.02)
    for i in range(5):
        rs.log.append(f"r{i}".encode())
    rs.group.drain()
    relog = Log.open(rs.servers[0].device, LogConfig(capacity=CAP))
    assert [p for _, p in relog.iter_records()] == \
        [f"r{i}".encode() for i in range(5)]
    rs.shutdown()


def test_quorum_error_raised_when_unreachable():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=3)
    rs.fail_backup("node1")
    with pytest.raises(QuorumError):
        rs.log.append(b"x")
    rs.shutdown()


def test_replicate_batch_is_one_wire_round():
    """Two segments through replicate_batch are one doorbell-batched
    wire round (exactly one transport op), vs two for per-seg calls."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2)
    dev = rs.primary_dev
    off = rs.log.ring_off
    dev.write(off, b"A" * 256)
    dev.write(off + 1024, b"B" * 256)
    t = rs.transports[0]
    ops_before = t._ops
    backup = rs.servers[0].device
    flushes = backup.stats.flushes
    rs.group.replicate_batch(dev, [(off, 256), (off + 1024, 256)])
    assert t._ops == ops_before + 1
    # both ranges really landed + were persisted remotely, one
    # persistence primitive per range
    assert backup.read(off, 256) == b"A" * 256
    assert backup.read(off + 1024, 256) == b"B" * 256
    assert backup.stats.flushes == flushes + 2
    # two independent rounds of the same shape are two wire ops
    rs.group.replicate(dev, off, off, 256)
    rs.group.replicate(dev, off + 1024, off + 1024, 256)
    assert t._ops == ops_before + 3
    rs.shutdown()


def test_force_across_wrap_is_single_quorum_round():
    """A force whose range wraps the ring replicates both segments in ONE
    quorum round (one transport op), not one round per segment."""
    cap = 1024
    rs = build_replica_set(mode="local+remote", capacity=cap, n_backups=1,
                           write_quorum=2)
    log = rs.log
    log.append(b"a" * 200)                    # lsn 1: [0, 224)
    log.append(b"b" * 200)                    # lsn 2: [224, 448)
    log.cleanup(1)
    log.cleanup(2)                            # head advances to 448
    rid3, v3 = log.reserve(400)               # [448, 872)
    v3[:] = b"c" * 400
    log.complete(rid3)
    rid4, v4 = log.reserve(120)               # pad @872, record wraps to 0
    v4[:] = b"d" * 120
    log.complete(rid4)
    t = rs.transports[0]
    ops_before = t._ops
    log.force(rid4)                           # range [448, cap) + [0, 144)
    assert t._ops == ops_before + 1, "wrap force took >1 replication round"
    relog = Log.open(rs.servers[0].device, LogConfig(capacity=cap))
    got = dict(relog.iter_records())
    assert got[rid3] == b"c" * 400 and got[rid4] == b"d" * 120
    rs.shutdown()


def test_write_and_force_segs_matches_per_seg_stats():
    """Local flush accounting of the multi-seg primitive is identical to
    the per-seg path (one flush+fence per segment)."""
    dev = PMEMDevice(1 << 16)
    dev.write(0, b"x" * 128)
    dev.write(4096, b"y" * 128)
    f0 = dev.stats.flushes
    write_and_force_segs(dev, [(0, 128), (4096, 128)])
    assert dev.stats.flushes == f0 + 2
    assert dev.stats.fences == dev.stats.flushes


def test_broadcast_bytes_parallel_quorum_and_eviction():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    rs.fail_backup("node1")
    rs.group.broadcast_bytes(b"epoch!", 0)    # quorum met: local + node2
    assert rs.servers[1].device.read(0, 6) == b"epoch!"
    rs.group.drain()
    assert any(t.closed for t in rs.transports)
    rs.fail_backup("node2")
    with pytest.raises(QuorumError):
        rs.group.broadcast_bytes(b"epoch!!", 0)
    rs.shutdown()


def test_drain_surfaces_programming_errors():
    """Non-transport exceptions from straggler ops must not be swallowed."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=1)
    boom = RuntimeError("bug in op")

    def bad_op(t):
        raise boom

    rs.group._submit(rs.transports[0], bad_op)
    with pytest.raises(RuntimeError):
        rs.group.drain()
    rs.shutdown()
