"""Replication, quorum recovery, diverging histories, fencing, failover."""

import numpy as np
import pytest

from repro.core import (CopyAccessor, ClusterManager, Log, LogConfig, Node,
                        PMEMDevice, QuorumError, RecoveryError,
                        build_replica_set, device_size, quorum_recover)
from repro.core.log import ring_offset
from repro.core.transport import (ReplicaServer, ReplicationGroup, Transport,
                                  TransportError)

pytestmark = pytest.mark.slow   # spins up replica servers per test

CAP = 1 << 16


def accessors_for(rs, include_primary=True, only=None):
    accs = []
    devs = rs.server_devices()
    for name, dev in devs.items():
        if only is not None and name not in only:
            continue
        if name == rs.primary_id and not include_primary:
            continue
        accs.append(CopyAccessor.for_device(name, dev))
    return accs


def test_replication_mirrors_bytes_to_backups():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=3)
    for i in range(20):
        rs.log.append(f"record-{i}".encode())
    ring = rs.primary_dev.read(0, ring_offset() + CAP)
    for s in rs.servers:
        assert s.device.read(0, len(ring)) == ring
    # backups individually recoverable
    for s in rs.servers:
        relog = Log.open(s.device, LogConfig(capacity=CAP))
        assert [p for _, p in relog.iter_records()] == \
            [f"record-{i}".encode() for i in range(20)]


def test_write_quorum_tolerates_backup_failure():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)          # N=3, W=2: 1 failure ok
    rs.log.append(b"a")
    rs.fail_backup("node1")
    rs.log.append(b"b")                              # still meets W=2
    assert rs.log.durable_lsn == 2
    # failed transport evicted once the straggler harvest has run (the
    # W-th-ack fast path no longer waits for the failure in-line)
    rs.group.drain()
    assert any(t.closed for t in rs.transports)


def test_write_quorum_failure_raises():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=3)          # N=3, W=3: no failures ok
    rs.log.append(b"a")
    rs.fail_backup("node1")
    with pytest.raises(QuorumError):
        rs.log.append(b"b")


def test_remote_only_mode():
    rs = build_replica_set(mode="remote_only", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(5):
        rs.log.append(f"r{i}".encode())
    # all durable copies are remote; each is a complete log
    for s in rs.servers:
        relog = Log.open(s.device, LogConfig(capacity=CAP))
        assert len(list(relog.iter_records())) == 5


def test_quorum_recovery_normal():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(10):
        rs.log.append(f"x{i}".encode())
    img, report = quorum_recover(accessors_for(rs), rs.cfg, write_quorum=2,
                                 local_name=rs.primary_id)
    assert report.new_epoch == report.old_epoch + 1
    relog = Log.open(img, LogConfig(capacity=CAP))
    assert len(list(relog.iter_records())) == 10
    assert relog.stats()["epoch"] == report.new_epoch


def test_quorum_recovery_repairs_lagging_backup():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(5):
        rs.log.append(f"x{i}".encode())
    rs.fail_backup("node2")                  # node2 stops receiving
    for i in range(5, 10):
        rs.log.append(f"x{i}".encode())
    img, report = quorum_recover(accessors_for(rs), rs.cfg, write_quorum=2,
                                 local_name=rs.primary_id)
    assert "node2" in report.repaired
    # node2 now holds the full history
    relog = Log.open(rs.servers[1].device, LogConfig(capacity=CAP))
    assert len(list(relog.iter_records())) == 10


def test_quorum_recovery_primary_lost():
    """Fig. 7b worst case: primary media gone; rebuild from backups."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(10):
        rs.log.append(f"y{i}".encode())
    accs = accessors_for(rs, include_primary=False)
    img, report = quorum_recover(accs, rs.cfg, write_quorum=2,
                                 local_name="node0-rebuilt")
    relog = Log.open(img, LogConfig(capacity=CAP))
    assert [p for _, p in relog.iter_records()] == \
        [f"y{i}".encode() for i in range(10)]


def test_repair_ships_only_differing_chunks():
    """Regression for the §4.2 idempotence argument: a one-line divergence
    must cost ~a chunk on the wire, not the whole golden image (the old
    repair rewrote everything on a single differing byte)."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(20):
        rs.log.append(f"record-{i}".encode())
    rs.group.drain()      # settle in-flight W-th-ack stragglers first
    # diverge ONE cache line inside node2's ring
    node2 = rs.servers[1].device
    node2.write(ring_offset() + 256, b"\xff" * 64)
    node2.persist(ring_offset() + 256, 64)
    image_size = ring_offset() + CAP
    img, report = quorum_recover(accessors_for(rs), rs.cfg, write_quorum=2,
                                 local_name=rs.primary_id)
    assert "node2" in report.repaired
    assert 0 < report.repair_bytes["node2"] < image_size // 16, \
        f"1-line divergence shipped {report.repair_bytes['node2']} bytes"
    # an in-sync copy only receives the superline epoch bump
    assert "node1" not in report.repaired
    assert report.repair_bytes["node1"] <= ring_offset()
    # and the repair actually took: node2 re-opens to the full history
    relog = Log.open(node2, LogConfig(capacity=CAP))
    assert [p for _, p in relog.iter_records()] == \
        [f"record-{i}".encode() for i in range(20)]


def test_read_quorum_not_met():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)          # R = 3 - 2 + 1 = 2
    rs.log.append(b"z")
    accs = accessors_for(rs, only={"node1"})         # only 1 of 3 readable
    with pytest.raises(RecoveryError):
        quorum_recover(accs, rs.cfg, write_quorum=2)


def test_no_readable_copy_raises_recovery_error():
    # one accessor against W=2 asks for a read quorum of 0: an empty
    # (never written) copy must still fail as a RecoveryError
    blank = PMEMDevice(device_size(CAP))
    with pytest.raises(RecoveryError, match="read quorum not met"):
        quorum_recover([CopyAccessor.for_device("node1", blank)],
                       LogConfig(capacity=CAP), write_quorum=2)


def test_diverging_histories_epoch_resolution():
    """The paper's §4.2 A/B/C example, verbatim."""
    size = device_size(CAP)
    A = PMEMDevice(size, name="A")
    B = PMEMDevice(size, name="B")
    C = PMEMDevice(size, name="C")
    cfg = LogConfig(capacity=CAP)
    for d in (A, B, C):
        Log.create(d, cfg)

    # A writes X at LSN 1 (replication to B, C failed), then crashes.
    logA = Log.open(A, cfg)
    logA.append(b"X")

    # Recovery reads B and C (A is down): consistent, epoch -> 2.
    accsBC = [CopyAccessor.for_device("B", B), CopyAccessor.for_device("C", C)]
    _, rep1 = quorum_recover(accsBC, cfg, write_quorum=2)
    assert rep1.new_epoch == 2

    # B and C write Y at LSN 1, then crash.
    for d in (B, C):
        lg = Log.open(d, cfg)
        lg.append(b"Y")

    # Recovery reads A and B: A has (epoch 1, X), B has (epoch 2, Y).
    accsAB = [CopyAccessor.for_device("A", A), CopyAccessor.for_device("B", B)]
    img, rep2 = quorum_recover(accsAB, cfg, write_quorum=2, local_name="A")
    assert rep2.old_epoch == 2 and rep2.new_epoch == 3
    assert rep2.chosen == "B"            # max-epoch copy wins
    # A must have been repaired to Y — the X history is discarded
    for name, dev in (("A", A), ("img", img)):
        relog = Log.open(dev, cfg)
        assert [p for _, p in relog.iter_records()] == [b"Y"], name


def test_recovery_is_idempotent_under_repeated_failures():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(7):
        rs.log.append(f"i{i}".encode())
    accs = accessors_for(rs)
    img1, r1 = quorum_recover(accs, rs.cfg, write_quorum=2,
                              local_name=rs.primary_id)
    img2, r2 = quorum_recover(accs, rs.cfg, write_quorum=2,
                              local_name=rs.primary_id)
    assert r2.new_epoch == r1.new_epoch + 1
    a = Log.open(img1, LogConfig(capacity=CAP))
    b = Log.open(img2, LogConfig(capacity=CAP))
    assert list(a.iter_records()) == list(b.iter_records())


def test_primary_failover_fences_old_primary():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    rs.log.append(b"before-failover")
    nodes = [Node("node0")] + [Node(s.server_id, server=s)
                               for s in rs.servers]
    cm = ClusterManager(nodes)
    assert cm.primary == "node0"
    events = []
    cm.on_primary_change(lambda old, new: events.append((old, new)))
    new_primary = cm.report_failure("node0")
    assert new_primary == "node1" and events == [("node0", "node1")]
    # the zombie old primary can no longer replicate (fenced)
    with pytest.raises(QuorumError):
        rs.log.append(b"zombie-write")
    # new primary recovers from surviving copies and continues
    accs = accessors_for(rs, include_primary=False)
    img, rep = quorum_recover(accs, rs.cfg, write_quorum=2,
                              local_name="node1")
    relog = Log.open(img, LogConfig(capacity=CAP))
    assert [p for _, p in relog.iter_records()] == [b"before-failover"]
    relog.append(b"after-failover")   # unreplicated continuation on new node
    assert relog.durable_lsn == 2


# --------------------------------------------------------------------- #
# Transport.reopen edge cases (DESIGN.md §11 satellite)
# --------------------------------------------------------------------- #
def test_reopen_with_pending_salvage_stash_reissues_wire_images():
    """Reopen a lane while the salvage stash still holds its post-time
    wire images: the next force leader bundles the stash, the staged
    image lands on the reopened lane, and the backup ends byte-identical
    — no gap, no full-range re-send."""
    import time
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=3, pipeline_depth=2)
    log = rs.log
    for i in range(4):
        log.append(f"base-{i}".encode() * 4)      # durable baseline
    rid, ptr = log.reserve(64)
    ptr[:] = b"m" * 64
    log.complete(rid)
    rs.transports[0].inject(delay_s=0.05)         # node1's ack is the late one
    log.force(rid, wait=False)
    rs.kill_backup_midwire("node1")               # round fails mid-wire
    st = log.stats()
    assert st["salvage_pending"] >= 1, "no stash to exercise (test inert)"
    assert st["salvage_stash_bytes"] > 0          # wire image really held
    # RAW reopen — not recover_backup: no resync, no lane drain.  The
    # stash must survive the reopen and cover the lane's hole itself.
    for t in rs.transports:
        if t.server.server_id == "node1":
            t.reopen()
            t.server.unfence(t.primary_id)
    rid2, ptr2 = log.reserve(32)
    ptr2[:] = b"f" * 32
    log.complete(rid2)
    assert log.force(rid2, timeout=5.0) >= rid2   # stash + fresh, one round
    assert log.stats()["salvage_rounds"] >= 1
    assert log.stats()["salvage_pending"] == 0
    log.drain(timeout=5.0)
    rs.group.drain(timeout=5.0)
    ring = rs.primary_dev.read(0, ring_offset() + CAP)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    rs.shutdown()


def test_reopen_racing_failover_fence_rejects_old_epoch_writes():
    """Reopening a lane after a failover must NOT re-admit the deposed
    primary: epoch fencing lives at the server, so the old primary's
    writes bounce with TransportError and its forces fail their quorum
    even through a freshly reopened transport."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2, pipeline_depth=2)
    nodes = [Node("node0")] + [Node(s.server_id, server=s)
                               for s in rs.servers]
    cm = ClusterManager(nodes)
    cm.attach_log(rs.log)
    for i in range(4):
        rs.log.append(f"pre-{i}".encode() * 4)
    assert cm.report_failure("node0") == "node1"  # fence + election
    t = rs.transports[0]
    t.reopen()                                    # the race: lane reopened
    assert not t.closed
    assert t.server.is_fenced("node0")            # ...but the fence held
    with pytest.raises(TransportError):
        t.write_imm_bytes(b"x" * 64, ring_offset())
    # the old primary's log cannot commit anything new either
    rid, ptr = rs.log.reserve(8)
    ptr[:] = b"o" * 8
    rs.log.complete(rid)
    with pytest.raises(QuorumError):
        rs.log.force(rid, timeout=5.0)
    rs.shutdown()
