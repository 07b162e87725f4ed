"""Chaos soak harness (DESIGN.md §11): seeded multi-fault schedules.

Each schedule composes faults drawn from a seeded RNG against a live
three-copy replica set: bit rot on committed records (any copy, primary
included), a backup partition ridden out in degraded-quorum mode, or a
mid-wire backup kill with an in-flight pipelined round, followed by
rejoin-with-resync, more traffic, and a scrub-to-clean verify.

Invariants checked on every schedule:
  * every acked record survives with its exact payload (digest == the
    no-fault control, which is the generator function itself);
  * the scrubber detects and repairs 100% of the injected corruption
    still present at scrub time (resync may legitimately repair rot on
    a partitioned backup first);
  * total repair traffic is a strict subset of the committed golden
    image — self-healing never degenerates into full re-replication;
  * repairs are the only extra writes the primary's device sees.
"""

import random
import threading
import time

import numpy as np
import pytest

from repro.core import (ClusterManager, FreqPolicy, HeartbeatConfig,
                        IngestConfig, IngestEngine, Node, ScrubConfig,
                        Scrubber, build_replica_set)
from repro.core.log import (FLAG_CLEANED, FLAG_PAD, FLAG_VALID, _REC_HDR,
                            _first_bad_payload, ring_offset)

pytestmark = pytest.mark.slow

C_CAP = 1 << 16
N_SCHEDULES = 64


def _payload(lsn: int) -> bytes:
    return bytes([(lsn * 37 + 11) & 0xFF]) * (40 + (lsn % 4) * 8)


def _copy_devs(rs):
    devs = {"node0": rs.primary_dev}
    devs.update({s.server_id: s.device for s in rs.servers})
    return devs


def _is_clean(dev, log, lsn) -> bool:
    """The scrubber's own validation, applied to one record on one copy."""
    rec = log._recs[lsn]
    raw = dev.read(rec.off, rec.extent)
    hl, hs, hc, hf = _REC_HDR.unpack_from(raw, 0)
    if hf & FLAG_CLEANED and hl == lsn and hs == rec.size:
        return True
    if hl != lsn or hs != rec.size or not hf & FLAG_VALID or hf & FLAG_PAD:
        return False
    return _first_bad_payload(raw, [(0, 0, lsn, rec.size, hc, hf)]) is None


def _inject_rot(rs, rng, np_rng, n, exclude=()):
    """Corrupt up to ``n`` distinct committed records, each on one
    randomly chosen copy (distinct LSNs guarantee a clean donor exists).
    Returns the (copy, lsn) pairs whose bytes really changed — an odd
    number of flips in the same bit position can cancel out."""
    log = rs.log
    devs = _copy_devs(rs)
    committed = [lsn for lsn, r in sorted(log._recs.items())
                 if lsn <= log.durable_lsn and not r.pad
                 and log._head_lsn <= lsn]
    rng.shuffle(committed)
    injected = []
    for lsn in committed[:n]:
        name = rng.choice([c for c in devs if c not in exclude])
        rec = log._recs[lsn]
        dev = devs[name]
        before = dev.read(rec.off, rec.extent)
        dev.corrupt(rec.off + 24, rec.size, np_rng, nbits=8)
        if dev.read(rec.off, rec.extent) != before:
            injected.append((name, lsn))
    return injected


@pytest.mark.parametrize("seed", range(N_SCHEDULES))
def test_chaos_schedule(seed):
    rng = random.Random(seed)
    np_rng = np.random.default_rng(seed)
    fault = rng.choice(["none", "partition", "partition",
                        "midwire", "midwire"])
    depth = rng.choice([1, 2, 4])
    wq = 3 if fault == "partition" else 2
    victim = rng.choice(["node1", "node2"])
    vt_idx = 0 if victim == "node1" else 1
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=wq,
                           device_mode="strict", pipeline_depth=depth)
    cm = ClusterManager([Node(rs.primary_id)] +
                        [Node(s.server_id, server=s) for s in rs.servers])
    cm.attach_log(rs.log)
    cm.attach_group(rs.group, allow_degraded=True, min_write_quorum=2)
    acked = {}

    def put(k=1):
        for _ in range(k):
            lsn = rs.log.append(_payload(rs.log._next_lsn))
            acked[lsn] = _payload(lsn)

    # phase A: healthy traffic
    put(8)

    # phase B: the scheduled fault, ridden out live
    if fault == "partition":
        rs.fail_backup(victim)
        cm.report_failure(victim)
        assert cm.stats()["degraded"] and rs.group.write_quorum == 2
        put(8)                               # commits on surviving copies
    elif fault == "midwire":
        rs.transports[vt_idx].inject(delay_s=0.03)
        inflight = b"\x5a" * 64
        rid, _ = rs.log.reserve(len(inflight))
        rs.log.copy(rid, inflight)
        rs.log.complete(rid)
        rs.log.force(rid, wait=False)        # round in flight on the wire
        rs.kill_backup_midwire(victim, settle_s=0.03)
        acked[rid] = inflight
        put(7)                               # W=2: local + survivor
    else:
        put(8)

    # bit rot lands while the fault is still open
    injected = _inject_rot(rs, rng, np_rng, n=rng.randint(1, 3))

    # phase C: rejoin with online resync, then more healthy traffic
    if fault != "none":
        rs.transports[vt_idx].inject()
        rep = rs.recover_backup(victim)
        assert rep.server_id == victim
        if fault == "partition":
            assert 0 < rep.repair_bytes < rep.sealed_bytes
            cm.report_recovery(victim)
            assert not cm.stats()["degraded"]
            assert rs.group.write_quorum == 3
    put(8)
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)

    # which copies are still corrupt at the injected LSNs?  Resync can
    # cut both ways: it repairs rot that landed on the partitioned
    # copy's stale image, but rot on the PRIMARY propagates to the
    # rejoining backup (resync trusts the primary image) — the scrubber
    # is the layer that catches that, so count every dirty copy.
    devs = _copy_devs(rs)
    bad_lsns = {lsn for _, lsn in injected}
    still_bad = {(name, lsn) for lsn in bad_lsns for name in devs
                 if not _is_clean(devs[name], rs.log, lsn)}
    pw0 = rs.primary_dev.stats.bytes_written

    sc = Scrubber.from_replica_set(rs)
    reports = sc.scrub_to_completion(max_passes=64)
    found = {cr for rep in reports for cr in rep.corrupt_records}

    # 1. detection + repair is exact: everything injected, nothing else
    assert found == still_bad
    st = sc.stats()
    assert st["repaired"] == len(still_bad) and st["unrepairable"] == 0
    assert reports[-1].complete and reports[-1].corrupt == 0

    # 2. repair traffic ≪ golden image: chunked diffs, not re-replication
    golden = sum(r.extent for lsn, r in rs.log._recs.items()
                 if lsn <= rs.log.durable_lsn and not r.pad)
    if still_bad:
        assert 0 < st["repair_bytes"] < golden
    else:
        assert st["repair_bytes"] == 0

    # 3. the primary device only saw writes the scrubber can account for
    pw_extra = rs.primary_dev.stats.bytes_written - pw0
    assert pw_extra <= st["repair_bytes"]
    if not any(name == "node0" for name, _ in still_bad):
        assert pw_extra == 0

    # 4. every acked record survived with its control payload
    got = dict(rs.log.iter_records())
    for lsn, payload in acked.items():
        assert got[lsn] == payload, f"acked lsn {lsn} lost or mangled"

    # 5. all three copies converged byte-for-byte
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    for srv in rs.servers:
        assert srv.device.read(0, len(ring)) == ring
    rs.shutdown()


# --------------------------------------------------------------------- #
# hot-path interaction soaks
# --------------------------------------------------------------------- #
def test_soak_scrub_under_hot_ingest():
    """Background scrubber vs a live multi-producer ingest engine: the
    scrub yields to load (deferred passes), still repairs injected rot,
    and never costs an acked record."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=2,
                           device_mode="strict", pipeline_depth=4)
    eng = rs.attach_ingest(IngestConfig(flush_records=4),
                           policy=FreqPolicy(4))
    warm = [eng.append(_payload(i + 1)) for i in range(8)]
    for t in warm:
        t.wait(timeout=30)
    np_rng = np.random.default_rng(99)
    rec = rs.log._recs[3]
    dev = rs.servers[0].device
    before = dev.read(rec.off, rec.extent)
    dev.corrupt(rec.off + 24, rec.size, np_rng, nbits=8)
    assert dev.read(rec.off, rec.extent) != before
    sc = Scrubber.from_replica_set(rs, cfg=ScrubConfig(interval_s=0.002))
    sc.start()
    tickets = []

    def producer(tid):
        for i in range(20):
            tickets.append(eng.append(b"%d:%d" % (tid, i) * 8, timeout=30))

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    eng.drain(timeout=30)
    deadline = time.monotonic() + 10.0
    while sc.stats()["repaired"] < 1 and time.monotonic() < deadline:
        time.sleep(0.01)
    sc.stop()
    st = sc.stats()
    assert st["repaired"] == 1 and st["corrupt_found"] == 1
    for t in tickets:
        assert t.wait(timeout=30) <= rs.log.durable_lsn
    sc.scrub_to_completion(max_passes=8)     # quiesced verify: all clean
    rs.shutdown()


def test_soak_resync_under_hot_ingest():
    """Online resync while the ingest engine keeps pumping: the log
    stays live through catch-up and cut-over, and the rejoined backup
    converges with the primary."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=2,
                           device_mode="strict", pipeline_depth=4)
    eng = rs.attach_ingest(IngestConfig(flush_records=4),
                           policy=FreqPolicy(4))
    for i in range(8):
        eng.append(_payload(i + 1)).wait(timeout=30)
    rs.kill_backup_midwire("node1")
    tickets = []
    stop = threading.Event()

    def producer():
        i = 0
        while not stop.is_set():
            tickets.append(eng.append(bytes([i & 0xFF]) * 48, timeout=30))
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
        th.join(timeout=30)
    assert rep.repair_bytes > 0
    eng.drain(timeout=30)
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)
    for t in tickets:
        assert t.wait(timeout=30) <= rs.log.durable_lsn
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    rs.shutdown()


def test_soak_heartbeat_failover_with_inflight_rounds():
    """Detector-driven failover while pipelined rounds are in flight:
    the partitioned lane is failed out on missed heartbeats, the open
    rounds retire at the degraded quorum, and nothing acked is lost."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=3,
                           device_mode="strict", pipeline_depth=4)
    hm = rs.attach_health(allow_degraded=True, min_write_quorum=2,
                          heartbeat=HeartbeatConfig(
                              interval_s=0.01, miss_threshold=2,
                              backoff_base_s=0.05, jitter=0.0))
    acked = {}
    for i in range(4):
        lsn = rs.log.append(_payload(i + 1))
        acked[lsn] = _payload(lsn)
    rs.transports[1].inject(delay_s=0.03)    # node2 slow: rounds dwell
    rids = []
    for _ in range(3):
        p = b"\xa5" * 48
        rid, _ = rs.log.reserve(len(p))
        rs.log.copy(rid, p)
        rs.log.complete(rid)
        rs.log.force(rid, wait=False)
        rids.append(rid)
    rs.transports[0].inject(drop=True)       # node1 partitions mid-flight
    now, evs = 0.0, []
    for _ in range(6):
        evs += hm.tick(now)
        now += 0.02
    assert ("down", "node1") in evs
    assert rs.group.write_quorum == 2        # degraded: W=3 -> 2
    rs.log.drain(timeout=10.0)               # in-flight rounds retire
    for rid in rids:
        acked[rid] = b"\xa5" * 48
        assert rid <= rs.log.durable_lsn
    rs.transports[1].inject()
    rs.transports[0].inject()                # node1 heals -> resync path
    for _ in range(10):
        evs += hm.tick(now)
        now += 0.1
    assert ("up", "node1") in evs
    assert rs.group.write_quorum == 3
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)
    got = dict(rs.log.iter_records())
    for lsn, payload in acked.items():
        assert got[lsn] == payload
    ring = rs.primary_dev.read(0, ring_offset() + rs.cfg.capacity)
    node1 = next(s for s in rs.servers if s.server_id == "node1")
    assert node1.device.read(0, len(ring)) == ring
    rs.shutdown()


# --------------------------------------------------------------------- #
# trim lifecycle interaction soaks (DESIGN.md §13)
# --------------------------------------------------------------------- #
#
# Bulk truncation joins the chaos roster: the watermark advance holds
# _alloc_lock + _issue_lock, so it serializes against scrub repair,
# resync cut-over, and salvage re-issue — these soaks drive each pair
# concurrently and check that no acked record above the head is lost,
# no trimmed record resurrects, and the copies still converge where
# bytes are defined (live record extents + the replicated trim slot).

from repro.core.log import TRIM_SLOT_SIZE, _trim_decode, trim_slot_offset


def _trim_slots_agree(rs):
    want = rs.log.trim_lsn
    assert _trim_decode(
        rs.primary_dev.read(trim_slot_offset(), TRIM_SLOT_SIZE)) == want
    for srv in rs.servers:
        assert _trim_decode(
            srv.device.read(trim_slot_offset(), TRIM_SLOT_SIZE)) == want


def _live_extents_converged(rs):
    log = rs.log
    for lsn, rec in sorted(log._recs.items()):
        if rec.pad or lsn < log._head_lsn or lsn > log.durable_lsn:
            continue
        gold = rs.primary_dev.read(rec.off, rec.extent)
        for srv in rs.servers:
            assert srv.device.read(rec.off, rec.extent) == gold, \
                f"live lsn {lsn} diverged on {srv.server_id}"


def _trim_keeper(rs, stop, keep=8, interval_s=0.003):
    """Background truncator: keep the newest ``keep`` durable records."""
    n = 0
    while not stop.is_set():
        d, h = rs.log.durable_lsn, rs.log.trim_lsn
        if d - keep > h:
            rs.trim(d - keep)
            n += 1
        time.sleep(interval_s)
    return n


def test_soak_trim_racing_scrub():
    """Background scrubber vs background truncator vs hot ingest: the
    repair loop re-checks the head under _alloc_lock, so a record
    trimmed between detection and repair is skipped, never written
    below the head — and the scrub still converges to a clean pass."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=2,
                           device_mode="strict", pipeline_depth=4)
    eng = rs.attach_ingest(IngestConfig(flush_records=4),
                           policy=FreqPolicy(4))
    acked = {}
    for i in range(12):
        p = _payload(i + 1)
        eng.append(p).wait(timeout=30)
        acked[i + 1] = p
    sc = Scrubber.from_replica_set(rs, cfg=ScrubConfig(interval_s=0.002))
    sc.start()
    stop = threading.Event()
    trimmer = threading.Thread(target=_trim_keeper, args=(rs, stop))
    trimmer.start()
    np_rng = np.random.default_rng(7)
    tickets = []

    def producer(tid):
        for i in range(20):
            p = b"%d:%d" % (tid, i) * 8
            t = eng.append(p, timeout=30)
            tickets.append((t, p))
            if i % 7 == 3:        # rot lands on the hot tail, racing both
                lsn = rs.log.durable_lsn
                rec = rs.log._recs.get(lsn)
                if rec is not None and not rec.pad:
                    rs.servers[tid % 2].device.corrupt(
                        rec.off + 24, rec.size, np_rng, nbits=8)
            time.sleep(0.001)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=30)
    eng.drain(timeout=30)
    stop.set()
    trimmer.join(timeout=30)
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)

    # a deterministic final injection on a record that stays live, so
    # the quiesced verify proves repair (not just absence of faults)
    lsn = rs.log.durable_lsn
    rec = rs.log._recs[lsn]
    dev = rs.servers[0].device
    before = dev.read(rec.off, rec.extent)
    dev.corrupt(rec.off + 24, rec.size, np_rng, nbits=8)
    assert dev.read(rec.off, rec.extent) != before
    reports = sc.scrub_to_completion(max_passes=64)
    sc.stop()
    st = sc.stats()
    assert reports[-1].complete and reports[-1].corrupt == 0
    assert st["unrepairable"] == 0 and st["repaired"] >= 1
    assert rs.log.trim_lsn > 0 and rs.log.stats()["trimmed_records"] > 0
    got = dict(rs.log.iter_records())
    head = rs.log._head_lsn
    for lsn, p in acked.items():
        if lsn >= head:
            assert got[lsn] == p              # acked-never-lost
        else:
            assert lsn not in got             # trimmed-never-resurrected
    for t, p in tickets:
        lsn = t.wait(timeout=30)
        assert lsn <= rs.log.durable_lsn
        if lsn >= head:
            assert got[lsn] == p
    _trim_slots_agree(rs)
    _live_extents_converged(rs)
    rs.shutdown()


def test_soak_trim_racing_backup_resync():
    """Truncation while a backup is down AND while it resyncs: the
    rejoining copy must adopt the advanced watermark (meta re-diff in
    cut-over) and only the surviving suffix — records both appended and
    trimmed during its absence never reach it as live state."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=2,
                           device_mode="strict", pipeline_depth=4)
    eng = rs.attach_ingest(IngestConfig(flush_records=4),
                           policy=FreqPolicy(4))
    for i in range(8):
        eng.append(_payload(i + 1)).wait(timeout=30)
    rs.kill_backup_midwire("node1")
    # while node1 is gone: traffic + a watermark advance it never saw
    for i in range(8, 24):
        eng.append(_payload(i + 1)).wait(timeout=30)
    rs.trim(rs.log.durable_lsn - 8)
    assert rs.log.trim_lsn > 0
    stop = threading.Event()
    trimmer = threading.Thread(target=_trim_keeper, args=(rs, stop))
    trimmer.start()
    tickets = []

    def producer():
        i = 0
        while not stop.is_set():
            tickets.append(eng.append(bytes([i & 0xFF]) * 48, timeout=30))
            i += 1
            time.sleep(0.001)

    th = threading.Thread(target=producer)
    th.start()
    try:
        time.sleep(0.02)
        rep = rs.recover_backup("node1")     # resync races live trims
        time.sleep(0.02)
    finally:
        stop.set()
        th.join(timeout=30)
        trimmer.join(timeout=30)
    assert rep.server_id == "node1" and rep.repair_bytes > 0
    eng.drain(timeout=30)
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)
    # one more settled trim: the rejoined lane must replicate it too.
    # The trim returns at its write quorum (W=2 of 3), so the third
    # copy's lane may still hold it: settle the lanes before comparing
    if rs.log.durable_lsn - 4 > rs.log.trim_lsn:
        rs.trim(rs.log.durable_lsn - 4)
    rs.group.drain(timeout=10.0)
    for t in tickets:
        assert t.wait(timeout=30) <= rs.log.durable_lsn
    _trim_slots_agree(rs)
    _live_extents_converged(rs)
    # the suffix a fresh replacement would recover from the backups
    # alone is exactly the post-trim view
    from repro.core import CopyAccessor, Log, LogConfig, quorum_recover
    accs = [CopyAccessor.for_device(s.server_id, s.device)
            for s in rs.servers]
    img, _ = quorum_recover(accs, rs.cfg, write_quorum=2,
                            local_name="node0-new")
    relog = Log.open(img, LogConfig(capacity=C_CAP))
    assert relog._head_lsn == rs.log._head_lsn
    assert dict(relog.iter_records()) == dict(rs.log.iter_records())
    rs.shutdown()


def test_soak_trim_racing_salvage_stash():
    """A mid-wire backup death leaves a failed round in the salvage
    stash (un-durable LSNs).  Trimming the durable prefix while the
    stash is pending must neither reclaim the stashed records (they are
    above the durable LSN, so `trim` refuses by construction) nor lose
    them: after the lane heals, the bundled salvage re-issue retires
    them above the new head."""
    rs = build_replica_set(mode="local+remote", capacity=C_CAP,
                           n_backups=2, write_quorum=2,
                           device_mode="strict", pipeline_depth=4)
    acked = {}
    for i in range(10):
        lsn = rs.log.append(_payload(rs.log._next_lsn))
        acked[lsn] = _payload(lsn)
    pre_durable = rs.log.durable_lsn
    rs.transports[0].inject(delay_s=0.03)    # node1 slow: round dwells
    inflight = b"\x5a" * 64
    rid, _ = rs.log.reserve(len(inflight))
    rs.log.copy(rid, inflight)
    rs.log.complete(rid)
    rs.log.force(rid, wait=False)            # round in flight on the wire
    rs.kill_backup_midwire("node1", settle_s=0.03)
    acked[rid] = inflight
    # the stashed round's LSN may not be durable yet; the prefix below
    # it is — reclaim that while the stash is open
    rs.trim(pre_durable - 2)
    assert rs.log.trim_lsn == pre_durable - 2
    # more traffic at the degraded quorum: the salvage bundle rides
    # first on the next force and retires on the surviving lanes
    for _ in range(6):
        lsn = rs.log.append(_payload(rs.log._next_lsn))
        acked[lsn] = _payload(lsn)
    assert rid <= rs.log.durable_lsn         # stash salvaged, not lost
    rs.transports[0].inject()
    rep = rs.recover_backup("node1")
    assert rep.server_id == "node1"
    rs.trim(rs.log.durable_lsn - 4)          # and trim again, healed
    rs.log.drain(timeout=10.0)
    rs.group.drain(timeout=10.0)
    got = dict(rs.log.iter_records())
    head = rs.log._head_lsn
    for lsn, p in acked.items():
        if lsn >= head:
            assert got[lsn] == p
        else:
            assert lsn not in got
    _trim_slots_agree(rs)
    _live_extents_converged(rs)
    rs.shutdown()
