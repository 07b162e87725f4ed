"""Self-healing replica lifecycle (DESIGN.md §11): background scrub,
online backup resync, heartbeat failure detection, degraded quorum."""

import threading
import time

import numpy as np
import pytest

from repro.core import (ClusterManager, FailureDetector, FreqPolicy,
                        HealthMonitor, HeartbeatConfig, Node, QuorumError,
                        ScrubConfig, Scrubber, build_replica_set)
from repro.core.log import ring_offset
from repro.core.pmem import CACHE_LINE

pytestmark = pytest.mark.slow   # spins up replica servers per test

CAP = 1 << 14


def _rs(n_backups=2, wq=None, depth=2, mode="strict", cap=CAP):
    return build_replica_set(mode="local+remote", capacity=cap,
                             n_backups=n_backups, write_quorum=wq,
                             device_mode=mode, pipeline_depth=depth)


def _fill(rs, n=12, size=48, freq=2):
    pol = FreqPolicy(freq)
    lsns = []
    for i in range(n):
        lsn = rs.log.append(bytes([(i * 37 + 11) & 0xFF]) * size)
        pol.on_complete(rs.log, lsn)
        lsns.append(lsn)
    pol.drain(rs.log)
    rs.group.drain(timeout=5.0)
    return lsns


def _corrupt_payload(dev, log, lsn, rng, nbits=8):
    rec = log._recs[lsn]
    before = dev.read(rec.off, rec.extent)
    dev.corrupt(rec.off + 24, rec.size, rng, nbits=nbits)
    return dev.read(rec.off, rec.extent) != before


# --------------------------------------------------------------------- #
# scrubber
# --------------------------------------------------------------------- #
def test_scrub_clean_log_finds_nothing():
    rs = _rs()
    _fill(rs)
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert rep.complete and rep.corrupt == 0 and rep.repair_bytes == 0
    assert rep.scanned_records == 12 * 3     # every record on every copy
    rs.shutdown()


def test_scrub_detects_and_repairs_backup_bit_rot():
    rs = _rs()
    lsns = _fill(rs)
    rng = np.random.default_rng(7)
    changed = _corrupt_payload(rs.servers[0].device, rs.log, lsns[3], rng)
    assert changed, "injected flips restored themselves; pick another seed"
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert rep.corrupt == 1 and rep.repaired == 1
    assert ("node1", lsns[3]) in rep.corrupt_records
    # chunk-diff repair: a few flipped bits cost at most one chunk per
    # differing range, nowhere near the record image
    assert 0 < rep.repair_bytes <= sc.cfg.chunk * rep.repair_ranges
    # converged: the next pass is clean
    rep2 = sc.scrub_once()
    assert rep2.complete and rep2.corrupt == 0
    rs.shutdown()


def test_scrub_repairs_primary_from_backup_quorum():
    """Corruption on the PRIMARY image is repaired from a clean backup
    copy — the scrubber has no privileged copy, only a quorum."""
    rs = _rs()
    lsns = _fill(rs)
    rng = np.random.default_rng(3)
    assert _corrupt_payload(rs.primary_dev, rs.log, lsns[7], rng)
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert rep.corrupt == 1 and rep.repaired == 1
    assert ("node0", lsns[7]) in rep.corrupt_records
    # the repaired primary serves the original payloads again
    payloads = dict(rs.log.iter_records())
    assert payloads[lsns[7]] == bytes([(7 * 37 + 11) & 0xFF]) * 48
    rs.shutdown()


def test_scrub_detects_header_corruption():
    rs = _rs()
    lsns = _fill(rs)
    rec = rs.log._recs[lsns[5]]
    dev = rs.servers[1].device
    dev.write(rec.off, b"\xff" * 8)          # clobber the header LSN
    dev.persist(rec.off, 8)
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert ("node2", lsns[5]) in rep.corrupt_records
    assert rep.repaired == rep.corrupt == 1
    rs.shutdown()


def test_scrub_budget_resumes_with_cursor():
    """A tight per-pass byte budget covers the prefix round-robin: no
    single pass is complete, but the union of passes is, and corruption
    anywhere is still found."""
    rs = _rs()
    lsns = _fill(rs, n=16)
    rng = np.random.default_rng(11)
    assert _corrupt_payload(rs.servers[0].device, rs.log, lsns[-2], rng)
    # budget fits ~2 records x 3 copies per pass
    sc = Scrubber.from_replica_set(
        rs, cfg=ScrubConfig(max_bytes_per_pass=600))
    reports = sc.scrub_to_completion(max_passes=64)
    assert len(reports) > 2                  # budget really sliced the work
    assert not reports[0].complete
    assert sc.stats()["corrupt_found"] == 1
    assert sc.stats()["repaired"] == 1
    rs.shutdown()


def test_scrub_defers_to_busy_engine_and_force_overrides():
    rs = _rs()
    _fill(rs)
    sc = Scrubber(rs.log, copies={"node0": rs.primary_dev},
                  load_signal=lambda: True)
    rep = sc.scrub_once()
    assert rep.deferred and rep.scanned_bytes == 0
    assert sc.stats()["deferred"] == 1
    rep = sc.scrub_once(force=True)
    assert not rep.deferred and rep.complete
    rs.shutdown()


def test_scrub_skips_tombstoned_records():
    rs = _rs()
    lsns = _fill(rs)
    rs.log.cleanup(lsns[2])                  # tombstone: payload is dead
    rs.group.drain(timeout=5.0)
    rng = np.random.default_rng(5)
    rec = rs.log._recs.get(lsns[2])
    if rec is not None:                      # not yet reclaimed by head
        rs.servers[0].device.corrupt(rec.off + 24, rec.size, rng, nbits=8)
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert rep.corrupt == 0                  # dead bytes are nobody's data
    rs.shutdown()


def test_scrub_unrepairable_when_no_clean_copy():
    rs = _rs(n_backups=1, wq=2)
    lsns = _fill(rs)
    rng = np.random.default_rng(13)
    assert _corrupt_payload(rs.primary_dev, rs.log, lsns[4], rng)
    assert _corrupt_payload(rs.servers[0].device, rs.log, lsns[4], rng)
    sc = Scrubber.from_replica_set(rs)
    rep = sc.scrub_once()
    assert rep.corrupt == 2
    assert rep.unrepairable == 2 and rep.repaired == 0
    rs.shutdown()


def test_scrub_background_thread_mode():
    rs = _rs()
    lsns = _fill(rs)
    rng = np.random.default_rng(17)
    assert _corrupt_payload(rs.servers[1].device, rs.log, lsns[1], rng)
    sc = Scrubber.from_replica_set(rs, cfg=ScrubConfig(interval_s=0.005))
    sc.start()
    deadline = time.monotonic() + 5.0
    while sc.stats()["repaired"] < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    sc.stop()
    assert sc.stats()["repaired"] == 1
    rs.shutdown()


# --------------------------------------------------------------------- #
# online backup resync
# --------------------------------------------------------------------- #
def test_resync_ships_chunks_not_image():
    """A backup that missed a stretch of appends rejoins by shipping
    only the differing chunks of the sealed prefix (repair_bytes ≪ the
    full region), and ends byte-identical to the primary."""
    rs = _rs(wq=2, cap=1 << 16)
    _fill(rs, n=8)
    rs.kill_backup_midwire("node1")
    _fill(rs, n=8)                           # W=2 still met without node1
    rep = rs.recover_backup("node1")
    assert rep is not None and rep.server_id == "node1"
    assert 0 < rep.repair_bytes < rep.sealed_bytes
    rs.log.drain(timeout=5.0)
    rs.group.drain(timeout=5.0)
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    # the rejoined lane is live again: new appends reach it
    rs.log.append(b"after-rejoin" * 4)
    rs.group.drain(timeout=5.0)
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    assert node1.device.read(0, len(ring)) == ring
    rs.shutdown()


def test_resync_in_sync_backup_costs_nothing():
    rs = _rs(wq=2)
    _fill(rs, n=6)
    rep = rs.recover_backup("node2")         # was never behind
    assert rep.repair_bytes == 0
    rs.shutdown()


def test_resync_under_hot_ingest_keeps_log_live():
    """Appends keep flowing WHILE the resync runs: the catch-up phase
    never blocks the pipeline, the cut-over is bounded by one issue-lock
    hold, and afterwards the rejoined backup converges with the
    primary."""
    rs = build_replica_set(mode="local+remote", capacity=1 << 16,
                           n_backups=2, write_quorum=2, pipeline_depth=4)
    pol = FreqPolicy(2, wait=False)
    _fill(rs, n=8)
    rs.kill_backup_midwire("node1")
    stop = threading.Event()
    appended = []

    def producer():
        i = 0
        while not stop.is_set():
            lsn = rs.log.append(bytes([(i * 29 + 5) & 0xFF]) * 64)
            try:
                pol.on_complete(rs.log, lsn)
            except Exception:
                pass
            appended.append(lsn)
            i += 1
            time.sleep(0.001)

    th = threading.Thread(target=producer)
    th.start()
    try:
        time.sleep(0.02)
        rep = rs.recover_backup("node1")
        time.sleep(0.02)
    finally:
        stop.set()
        th.join(timeout=10.0)
    assert rep.repair_bytes > 0
    FreqPolicy(1).drain(rs.log)
    rs.group.drain(timeout=5.0)
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    assert rs.log.durable_lsn == max(appended)
    rs.shutdown()


# --------------------------------------------------------------------- #
# transport heartbeat verb
# --------------------------------------------------------------------- #
def test_ping_fails_on_partition_not_on_eviction():
    rs = _rs()
    t = rs.transports[0]
    t.ping()                  # acked: returns without raising
    t.inject(drop=True)
    with pytest.raises(Exception):
        t.ping()
    t.inject()
    t.close()                 # evicted data lane: heartbeat QP still up
    t.ping()
    rs.shutdown()


def test_ping_does_not_consume_failure_schedule():
    rs = _rs()
    t = rs.transports[0]
    t.inject(fail_after_ops=2)
    for _ in range(50):
        t.ping()              # heartbeats must not advance the op count
    rs.log.append(b"a")       # op 1 and 2 land fine
    rs.log.append(b"b")
    assert rs.log.durable_lsn == 2
    rs.shutdown()


# --------------------------------------------------------------------- #
# failure detector + degraded quorum
# --------------------------------------------------------------------- #
def _cluster_for(rs, **attach):
    cm = ClusterManager([Node(rs.primary_id)] +
                        [Node(s.server_id, server=s) for s in rs.servers])
    cm.attach_log(rs.log)
    if attach:
        cm.attach_group(rs.group, **attach)
    return cm


def test_detector_needs_consecutive_misses():
    rs = _rs()
    cm = _cluster_for(rs)
    det = FailureDetector(cm, HeartbeatConfig(interval_s=0.01,
                                              miss_threshold=3))
    for t in rs.transports:
        det.register_transport(t)
    rs.transports[0].inject(drop=True)
    now, evs = 0.0, []
    evs += det.tick(now)
    now += 0.02
    evs += det.tick(now)                     # 2 misses: not down yet
    assert evs == [] and "node1" in cm.alive_nodes()
    rs.transports[0].inject()                # blip recovered
    now += 0.02
    evs += det.tick(now)                     # success resets the count
    rs.transports[0].inject(drop=True)
    for _ in range(3):
        now += 0.02
        evs += det.tick(now)
    assert evs == [("down", "node1")]
    assert "node1" not in cm.alive_nodes()
    assert det.stats()["down_nodes"] == ["node1"]
    rs.shutdown()


def test_detector_backoff_grows_and_rejoin_resyncs():
    rs = _rs(wq=2)
    _fill(rs, n=6)
    cm = _cluster_for(rs, allow_degraded=True, min_write_quorum=1)
    det = FailureDetector(cm, HeartbeatConfig(
        interval_s=0.01, miss_threshold=2, backoff_base_s=0.1,
        backoff_max_s=0.8, jitter=0.0))
    det.register_transport(rs.transports[0])
    resynced = []
    det.on_up(lambda nid: resynced.append(rs.recover_backup(nid)))
    rs.transports[0].inject(drop=True)
    now = 0.0
    for _ in range(3):
        det.tick(now)
        now += 0.02
    assert det.stats()["down_nodes"] == ["node1"]
    # down probes run on exponential backoff: 0.1, 0.2, 0.4, 0.8, 0.8
    st = det._state["node1"]
    dues = []
    for _ in range(5):
        now = st.next_due
        det.tick(now)
        dues.append(st.next_due - now)
    assert dues == pytest.approx([0.2, 0.4, 0.8, 0.8, 0.8])
    # node comes back: probe succeeds -> resync THEN report_recovery
    rs.transports[0].inject()
    det.tick(st.next_due)
    assert det.stats()["up_events"] == 1
    assert len(resynced) == 1 and resynced[0].server_id == "node1"
    assert "node1" in cm.alive_nodes()
    rs.shutdown()


def test_degraded_quorum_allows_writes_and_restores():
    """W=3 with a dead backup wedges strict clusters; with
    allow_degraded the effective W drops (alert raised), writes keep
    committing on the surviving copies, and the configured W is
    restored only after the node resyncs back in."""
    rs = _rs(wq=3)
    _fill(rs, n=4)
    cm = _cluster_for(rs, allow_degraded=True, min_write_quorum=2)
    rs.fail_backup("node1")
    cm.report_failure("node1")
    st = cm.stats()
    assert st["degraded"] and st["degraded_events"] == 1
    assert rs.group.write_quorum == 2
    rs.log.append(b"degraded-write" * 2)     # W=2: commits without node1
    assert rs.log.durable_lsn == 5
    # node returns: resync first, only then does quorum restore
    rs.transports[0].inject()
    rs.recover_backup("node1")
    assert rs.group.write_quorum == 2        # not yet: still reported dead
    cm.report_recovery("node1")
    st = cm.stats()
    assert not st["degraded"] and rs.group.write_quorum == 3
    rs.log.append(b"full-quorum" * 2)        # needs all three again
    assert rs.log.durable_lsn == 6
    rs.shutdown()


def test_strict_quorum_wedges_but_alerts():
    rs = _rs(wq=3)
    _fill(rs, n=2)
    cm = _cluster_for(rs, allow_degraded=False)
    rs.fail_backup("node1")
    cm.report_failure("node1")
    st = cm.stats()
    assert st["degraded"]                    # alert even in strict mode
    assert rs.group.write_quorum == 3        # ...but W never lowered
    rid, _ = rs.log.reserve(8)
    rs.log.copy(rid, b"w" * 8)
    rs.log.complete(rid)
    with pytest.raises(QuorumError):
        rs.log.force(rid, timeout=5.0)
    rs.shutdown()


def test_min_write_quorum_floor_holds():
    rs = _rs(wq=3)
    cm = _cluster_for(rs, allow_degraded=True, min_write_quorum=2)
    cm.report_failure("node1")
    cm.report_failure("node2")               # one reachable copy left
    assert rs.group.write_quorum == 2        # floored, not 1
    rs.shutdown()


# --------------------------------------------------------------------- #
# HealthMonitor: the bundle, end to end
# --------------------------------------------------------------------- #
def test_health_monitor_full_lifecycle_deterministic_ticks():
    """Partition a backup under a degraded-tolerant monitor: the
    detector fails it over, writes continue at the lowered quorum, the
    node comes back, the monitor resyncs it and restores W — all on a
    virtual clock, plus a scrub repair along the way."""
    rs = _rs(wq=3, cap=1 << 16)
    lsns = _fill(rs, n=8)
    hm = rs.attach_health(allow_degraded=True, min_write_quorum=2,
                          heartbeat=HeartbeatConfig(
                              interval_s=0.01, miss_threshold=2,
                              backoff_base_s=0.05, backoff_max_s=0.2,
                              jitter=0.0))
    rng = np.random.default_rng(23)
    assert _corrupt_payload(rs.servers[1].device, rs.log, lsns[2], rng)
    now = 0.0
    rs.transports[0].inject(drop=True)       # node1 partitioned
    evs = []
    for _ in range(8):
        evs += hm.tick(now)
        now += 0.02
    assert ("down", "node1") in evs
    assert hm.cluster.stats()["degraded"]
    assert rs.group.write_quorum == 2
    _fill(rs, n=4)                           # stays writable, W=2
    rs.transports[0].inject()                # node returns
    for _ in range(20):
        evs += hm.tick(now)
        now += 0.1
    assert ("up", "node1") in evs
    assert not hm.cluster.stats()["degraded"]
    assert rs.group.write_quorum == 3
    # the scrubber ran between heartbeats and fixed the bit rot
    assert hm.scrubber.stats()["repaired"] >= 1
    # rejoined node converged with the primary
    rs.log.drain(timeout=5.0)
    rs.group.drain(timeout=5.0)
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    st = hm.stats()
    assert st["detector"]["down_events"] == 1
    assert st["cluster"]["degraded_events"] == 1
    rs.shutdown()
