"""Crash-consistent log lifecycle (DESIGN.md §13): durable trim
watermark, checkpoint+truncate, O(tail) recovery, free-space
backpressure — the PR-9 tentpole surface.

The fault matrix rows (crash at every ordering point of
checkpoint → watermark-flush → reclaim) live in
test_resilience_matrix.py; the racing compositions (trim vs scrub,
trim vs resync, trim vs salvage) live in test_chaos_soak.py.  This
file covers the deterministic contracts.
"""

import threading

import numpy as np
import pytest

from repro.core import (CopyAccessor, LifecycleConfig, Log, LogConfig,
                        LogFullError, LogLifecycle, PMEMDevice, TrimError,
                        build_replica_set, device_size, quorum_recover)
from repro.core.log import (TRIM_SLOT_SIZE, _trim_decode, _trim_encode,
                            trim_slot_offset)

CAP = 1 << 14


def _p(lsn: int) -> bytes:
    return bytes([(lsn * 37 + 11) & 0xFF]) * 48


def _mklog(cap=CAP, mode="fast"):
    dev = PMEMDevice(device_size(cap), mode=mode)
    return dev, Log.create(dev, LogConfig(capacity=cap))


# --------------------------------------------------------------------------- #
# the watermark word
# --------------------------------------------------------------------------- #

def test_trim_word_roundtrip():
    for lsn in (0, 1, 7, 1 << 20, (1 << 48) - 1):
        assert _trim_decode(_trim_encode(lsn)) == lsn


def test_trim_word_rejects_garbage():
    assert _trim_decode(b"\x00" * 8) is None          # zeroed media
    assert _trim_decode(b"\xff" * 8) is None
    assert _trim_decode(b"\xde\xad\xbe\xef\x01\x02\x03\x04") is None


def test_trim_word_range():
    with pytest.raises(ValueError):
        _trim_encode(1 << 48)
    with pytest.raises(Exception):
        _trim_encode(-1)


def test_create_seeds_watermark_slot():
    dev, log = _mklog()
    assert log.read_trim_watermark() == 0
    assert _trim_decode(dev.read(trim_slot_offset(), TRIM_SLOT_SIZE)) == 0


# --------------------------------------------------------------------------- #
# bulk truncate semantics
# --------------------------------------------------------------------------- #

def test_trim_basic():
    dev, log = _mklog()
    for i in range(1, 11):
        log.append(_p(i))
    used_before = CAP - log.free_bytes
    log.trim(4)
    assert log.read_trim_watermark() == 4
    assert log.trim_lsn == 4
    got = dict(log.iter_records())
    assert sorted(got) == list(range(5, 11))
    for lsn, payload in got.items():
        assert payload == _p(lsn)
    st = log.stats()
    assert st["head_lsn"] == 5
    assert st["trimmed_records"] == 4
    assert st["trimmed_bytes"] > 0
    assert CAP - log.free_bytes < used_before


def test_trim_is_o1_bookkeeping_no_tombstone_walk():
    """Bulk truncate must not touch the trimmed records' ring bytes:
    no per-record tombstone writes, only the 8-byte slot + superline."""
    dev, log = _mklog()
    for i in range(1, 9):
        log.append(_p(i))
    recs = [log._recs[l] for l in range(1, 5)]
    before = [dev.read(r.off, r.extent) for r in recs]
    log.trim(4)
    after = [dev.read(r.off, r.extent) for r in recs]
    assert before == after      # reclaim is bookkeeping, not writes


def test_trim_noop_and_errors():
    dev, log = _mklog()
    for i in range(1, 7):
        log.append(_p(i))
    log.trim(0)                                    # no-op below head
    assert log.stats()["head_lsn"] == 1
    log.trim(3)
    log.trim(2)                                    # already trimmed: no-op
    assert log.stats()["head_lsn"] == 4
    with pytest.raises(TrimError):
        log.trim(log.durable_lsn + 5)              # beyond durable
    log.trim(log.durable_lsn)                      # whole chain: legal
    assert list(log.iter_records()) == []
    for i in range(7, 12):                         # ring reusable after
        assert log.append(_p(i)) == i
    assert sorted(dict(log.iter_records())) == list(range(7, 12))


def test_trim_survives_clean_reopen():
    dev, log = _mklog()
    for i in range(1, 11):
        log.append(_p(i))
    log.trim(6)
    relog = Log.open(dev, LogConfig(capacity=CAP))
    got = dict(relog.iter_records())
    assert sorted(got) == list(range(7, 11))
    assert relog.stats()["head_lsn"] == 7
    # lifecycle continues across generations: append + trim again
    for i in range(11, 15):
        assert relog.append(_p(i)) == i
    relog.trim(12)
    assert sorted(dict(relog.iter_records())) == [13, 14]


def test_trim_reuses_ring_many_generations():
    """10x ring capacity of appends through a small ring with periodic
    trim: the ring never fills and every surviving suffix is exact."""
    dev, log = _mklog(cap=1 << 13)
    payload = b"g" * 96
    total = 0
    lsn = 0
    while total < 10 * (1 << 13):
        lsn = log.append(payload)
        total += len(payload)
        if lsn % 32 == 0:
            log.trim(lsn - 8)       # keep a short tail
    got = sorted(dict(log.iter_records()))
    assert got and got[-1] == lsn
    assert got == list(range(got[0], lsn + 1))     # gapless suffix


# --------------------------------------------------------------------------- #
# crash windows around the watermark store
# --------------------------------------------------------------------------- #

STAGES = ("pre_watermark", "pre_watermark_flush", "post_watermark",
          "post_superline")


class _CrashAt(Exception):
    pass


@pytest.mark.parametrize("stage", STAGES)
@pytest.mark.parametrize("keep", [0.0, 0.5, 1.0])
def test_crash_during_trim_recovers_pre_or_post(stage, keep):
    """Power loss at every ordering point of the watermark advance:
    recovery lands on the pre-trim or post-trim view, never torn —
    acked records never lost, trimmed records never resurrected into
    a hole."""
    dev, log = _mklog(mode="strict")
    n, upto = 12, 7
    for i in range(1, n + 1):
        log.append(_p(i))

    def hook(s):
        if s == stage:
            raise _CrashAt(s)

    with pytest.raises(_CrashAt):
        log.trim(upto, _crash_hook=hook)
    survivor = dev.crash(np.random.default_rng(hash((stage, keep)) & 0xFFFF),
                         keep_probability=keep)
    relog = Log.open(survivor, LogConfig(capacity=CAP))
    got = dict(relog.iter_records())
    head = min(got) if got else n + 1
    assert head in (1, upto + 1), f"torn trim: head={head}"
    # acked-never-lost: the whole suffix above the adopted head is there
    assert sorted(got) == list(range(head, n + 1))
    for lsn, payload in got.items():
        assert payload == _p(lsn)
    # the slot itself is never torn: it decodes to a valid pre/post value
    wm = relog.read_trim_watermark()
    assert wm in (0, upto)


def test_corrupt_watermark_falls_back_to_full_scan():
    """Rotted slot bytes (not a torn store — arbitrary garbage) must
    not wedge recovery or truncate anything: the full scan runs."""
    dev, log = _mklog(mode="strict")
    for i in range(1, 9):
        log.append(_p(i))
    dev.write(trim_slot_offset(), b"\xde\xad\xbe\xef\x10\x32\x54\x76")
    dev.persist(trim_slot_offset(), TRIM_SLOT_SIZE)
    survivor = dev.crash(np.random.default_rng(3), keep_probability=0.0)
    relog = Log.open(survivor, LogConfig(capacity=CAP))
    assert relog.read_trim_watermark() is None
    assert sorted(dict(relog.iter_records())) == list(range(1, 9))


def test_stale_watermark_beyond_chain_is_ignored():
    """A watermark claiming more than the chain holds (e.g. slot from a
    torn future trim that never committed its superline, then lost
    records) must not wedge: recovery cross-checks and falls back."""
    dev, log = _mklog(mode="strict")
    for i in range(1, 6):
        log.append(_p(i))
    # forge a valid-CRC watermark far beyond next_lsn
    dev.write(trim_slot_offset(), _trim_encode(1000))
    dev.persist(trim_slot_offset(), TRIM_SLOT_SIZE)
    relog = Log.open(dev, LogConfig(capacity=CAP))
    assert sorted(dict(relog.iter_records())) == list(range(1, 6))


# --------------------------------------------------------------------------- #
# free-space backpressure
# --------------------------------------------------------------------------- #

def test_free_space_low_fires_once_per_crossing():
    dev, log = _mklog(cap=1 << 13)
    log.cfg.free_space_low_frac = 0.5
    calls = []
    log.on_free_space_low = lambda lg: calls.append(lg.durable_lsn)
    payload = b"x" * 200
    while log.free_bytes > (1 << 12):
        log.append(payload)
    for _ in range(4):                   # deeper into the low zone
        log.append(payload)
    assert len(calls) == 1               # latched: one fire per crossing
    assert log.space_low_triggers == 1
    log.trim(log.durable_lsn - 2)        # frees space -> rearms
    while log.free_bytes > (1 << 12):
        log.append(payload)
    assert len(calls) == 2               # next crossing fires again


def test_log_full_last_ditch_reclaim():
    """No threshold configured at all: LogFullError gives the callback
    one shot at reclaim and the reservation retries once."""
    dev, log = _mklog(cap=1 << 13)
    log.on_free_space_low = lambda lg: lg.trim(lg.durable_lsn - 1)
    payload = b"y" * 200
    for _ in range(200):                 # ~5x ring capacity, never full
        log.append(payload)
    assert log.full_reclaims >= 1
    assert log.space_low_triggers == 0   # threshold path never armed


def test_log_full_without_callback_still_raises():
    dev, log = _mklog(cap=1 << 13)
    payload = b"z" * 200
    with pytest.raises(LogFullError):
        for _ in range(200):
            log.append(payload)


# --------------------------------------------------------------------------- #
# checkpoint manager wiring + the lifecycle orchestrator
# --------------------------------------------------------------------------- #

def _ckpt_fixture(cap=1 << 15, keep_last=2):
    from repro.checkpoint.manager import CheckpointConfig, CheckpointManager
    from repro.checkpoint.store import ObjectStore, ReplicatedStore
    dev, log = _mklog(cap=cap)
    store = ReplicatedStore([ObjectStore("s0"), ObjectStore("s1")],
                            write_quorum=2)
    mgr = CheckpointManager(store, log,
                            CheckpointConfig(keep_last=keep_last))
    return dev, log, mgr


def test_checkpoint_gc_advances_trim_watermark():
    dev, log, mgr = _ckpt_fixture()
    state = {"w": np.arange(32, dtype=np.float32)}
    for step in range(1, 5):
        for i in range(6):
            mgr.journal({"step": step, "i": i})
        mgr.save(step, state, sync=True)
    removed = mgr.gc()
    assert removed == 2                       # keep_last=2 of 4
    ms = mgr.manifests()
    assert [m["step"] for _, m in ms] == [3, 4]
    # log head == oldest kept manifest: everything below it reclaimed
    assert log.stats()["head_lsn"] == ms[0][0]
    assert log.read_trim_watermark() == ms[0][0] - 1
    # journal records below the kept snapshot are gone; above survive
    js = mgr.journal_records()
    assert js and all(lsn > ms[0][0] for lsn, _ in js)
    step, got, _ = mgr.restore({"w": np.zeros(32, dtype=np.float32)})
    assert step == 4 and np.array_equal(got["w"], state["w"])


def test_checkpoint_gc_first_cycle_trims_behind_single_manifest():
    dev, log, mgr = _ckpt_fixture(keep_last=1)
    for i in range(10):
        mgr.journal({"i": i})
    lsn = mgr.save(1, {"w": np.ones(8)}, sync=True)
    assert mgr.gc() == 0                      # nothing deleted...
    assert log.stats()["head_lsn"] == lsn     # ...but the ring is freed


def test_lifecycle_orchestrator_cycle_and_attach():
    dev, log, mgr = _ckpt_fixture(cap=1 << 15, keep_last=1)
    state = {"w": np.arange(64, dtype=np.float32)}
    lc = LogLifecycle(mgr, state_fn=lambda: state,
                      cfg=LifecycleConfig(free_space_low_frac=0.4)).attach()
    rep = lc.checkpoint_and_trim()            # manual cycle
    assert rep.trigger == "manual" and rep.manifest_lsn >= 1
    payload = b"t" * 200
    total = 0
    while total < 6 * (1 << 15):              # 6x ring capacity
        log.append(payload)
        total += len(payload)
    assert lc.cycles > 1 and log.space_low_triggers >= 1
    assert log.full_reclaims == 0             # threshold kept us ahead
    st = lc.stats()
    assert st["reclaimed_bytes"] > 4 * (1 << 15)
    step, got, _ = mgr.restore({"w": np.zeros(64, dtype=np.float32)})
    assert np.array_equal(got["w"], state["w"])
    lc.detach()
    assert log.on_free_space_low is None


def test_ingest_engine_with_lifecycle_never_full():
    """Group-commit waves over a ring a fraction of the traffic size:
    the complete_batch-time callback checkpoint+trims under the wave
    stream and no ticket ever fails with LogFullError."""
    from repro.core import IngestConfig, IngestEngine
    dev, log, mgr = _ckpt_fixture(cap=1 << 15, keep_last=1)
    lc = LogLifecycle(mgr, state_fn=lambda: {"w": np.zeros(16)},
                      cfg=LifecycleConfig(free_space_low_frac=0.4)).attach()
    eng = IngestEngine(log, IngestConfig())
    n_threads, per = 4, 120
    errs = []

    def producer(tid):
        for i in range(per):
            try:
                eng.append(b"%d/%d" % (tid, i) * 16).wait(timeout=60)
            except Exception as exc:              # pragma: no cover
                errs.append(exc)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=120)
    assert not errs
    assert eng.stats()["acked"] == n_threads * per
    assert lc.cycles >= 1
    eng.close()


# --------------------------------------------------------------------------- #
# ack-history across the trimmed horizon (PR-9 satellite)
# --------------------------------------------------------------------------- #

def test_ack_history_boundary_returns_bound_not_none():
    dev, log = _mklog()
    log._ACK_LOG_CAP = 8                      # shadow: age out quickly
    for i in range(1, 14):
        log.append(_p(i))
    assert log._ack_base > 0                  # history actually aged
    t1 = log.durable_ack_time(1)
    t_recent = log.durable_ack_time(log.durable_lsn)
    assert t1 is not None                     # used to be None -> "now"
    assert t_recent is not None and t1 <= t_recent
    assert log.durable_ack_time(log.durable_lsn + 1) is None  # not durable
    # bulk path agrees with scalar
    assert log.durable_ack_times([1, log.durable_lsn]) == [t1, t_recent]


def test_ack_history_none_only_for_pre_process_records():
    dev, log = _mklog()
    for i in range(1, 6):
        log.append(_p(i))
    relog = Log.open(dev, LogConfig(capacity=CAP))
    # recovered records predate this process: no stamp is honest
    assert relog.durable_ack_time(1) is None
    relog.append(_p(6))
    assert relog.durable_ack_time(6) is not None


# --------------------------------------------------------------------------- #
# replicated trim
# --------------------------------------------------------------------------- #

def test_trim_replicates_watermark_to_backups():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=3)
    for i in range(1, 11):
        rs.log.append(_p(i))
    rs.trim(6)
    slot = rs.primary_dev.read(trim_slot_offset(), TRIM_SLOT_SIZE)
    assert _trim_decode(slot) == 6
    for srv in rs.servers:
        assert srv.device.read(trim_slot_offset(), TRIM_SLOT_SIZE) == slot
    # quorum recovery from the surviving copies lands on the post-trim
    # view: O(tail) scan, trimmed records never resurrected
    accs = [CopyAccessor.for_device(n, d)
            for n, d in rs.server_devices().items()]
    img, report = quorum_recover(accs, rs.cfg, write_quorum=2,
                                 local_name=rs.primary_id)
    relog = Log.open(img, LogConfig(capacity=CAP))
    assert sorted(dict(relog.iter_records())) == list(range(7, 11))
    rs.group.drain()
    rs.shutdown()


def test_resync_after_trim_ships_meta_and_suffix():
    """A backup that missed a trim while dead must come back with the
    advanced watermark and only the surviving suffix."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=2,
                           write_quorum=2)
    for i in range(1, 7):
        rs.log.append(_p(i))
    rs.kill_backup_midwire("node1", settle_s=0.01)
    for i in range(7, 13):
        rs.log.append(_p(i))                  # W=2 via node0+node2
    rs.trim(9)                                # node1 misses slot+superline
    rep = rs.recover_backup("node1")
    assert rep is not None and rep.repair_bytes > 0
    srv = next(s for s in rs.servers if s.server_id == "node1")
    assert _trim_decode(
        srv.device.read(trim_slot_offset(), TRIM_SLOT_SIZE)) == 9
    relog = Log.open(srv.device, LogConfig(capacity=CAP))
    assert sorted(dict(relog.iter_records())) == list(range(10, 13))
    # the rejoined lane carries subsequent traffic + trims normally
    for i in range(13, 16):
        rs.log.append(_p(i))
    rs.trim(13)
    # the trim returns at its write quorum (W=2 of 3): settle node1's
    # lane before comparing its copy of the slot
    rs.group.drain()
    assert srv.device.read(trim_slot_offset(), TRIM_SLOT_SIZE) == \
        rs.primary_dev.read(trim_slot_offset(), TRIM_SLOT_SIZE)
    rs.shutdown()


# --------------------------------------------------------------------------- #
# sharded / multi-tenant trim
# --------------------------------------------------------------------------- #

def test_router_trim_to_cut_and_overlay_recovery():
    from repro.apps.kvstore import MultiTenantKV
    kv = MultiTenantKV()
    kv.add_tenant("acme", n_shards=2, capacity=CAP)
    kv.add_tenant("umbrella", n_shards=1, capacity=CAP)
    for i in range(40):
        kv.put("acme", b"k%d" % i, b"v%d" % i)
        kv.put("umbrella", b"u%d" % (i % 7), b"w%d" % i)
    cut, tables = kv.checkpoint_and_trim()
    assert set(cut.durable) == set(kv.router.shard_ids)
    for sid in kv.router.shard_ids:
        st = kv.router.shard(sid).log.stats()
        assert st["trim_lsn"] == cut.durable[sid]
        assert st["head_lsn"] == cut.durable[sid] + 1
    # post-trim traffic lands above the cut
    for i in range(40, 55):
        kv.put("acme", b"k%d" % i, b"v%d" % i)
    kv.put("umbrella", b"u0", b"final")
    kv.flush()
    expect = {t: dict(kv._tables[t]) for t in kv.tenants()}
    kv.close()
    rec = kv.router.recover(parallel=False)
    # logs hold only the suffix; the snapshot tables overlay-restore
    got = MultiTenantKV.recover_tables(rec.logs, base_tables=tables)
    assert got == expect


def test_router_trim_shard_is_shard_isolated():
    from repro.core.router import LogRouter, ShardSpec
    r = LogRouter()
    r.add_shard(ShardSpec(shard_id="a", capacity=CAP))
    r.add_shard(ShardSpec(shard_id="b", capacity=CAP))
    for i in range(10):
        r.append(_p(i + 1), shard_id="a")
        r.append(_p(i + 1), shard_id="b")
    r.trim_shard("a", 6)
    assert r.shard("a").log.stats()["head_lsn"] == 7
    assert r.shard("b").log.stats()["head_lsn"] == 1   # untouched
    r.shutdown()
