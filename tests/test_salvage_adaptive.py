"""Partial-quorum salvage + adaptive pipeline depth (DESIGN.md §9).

Salvage: when a durability round fails, its already-acked (backup ×
range) deltas are kept; the next force leader re-issues ONLY what never
acked, reusing the wire images the NIC snapshotted at the original post
— no repeated local flush, no repeated DMA read, re-issue bytes strictly
below a full re-issue.

Adaptive depth: LogConfig.pipeline_depth becomes a ceiling; the
effective depth grows while posts outpace retirements, halves on a round
failure or slot timeout, and re-grows after a clean window.

Property tests are hypothesis-guarded with deterministic fallback sweeps
(the PR-1 pattern), so CI covers the invariants without hypothesis.
"""

import time

import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # deterministic sweeps still run without it
    class _AnyStrategy:
        def __getattr__(self, name):
            return lambda *a, **k: None

    st = _AnyStrategy()

    def settings(*a, **k):
        def deco(fn):
            return fn
        return deco

    def given(*a, **k):
        def deco(fn):
            return pytest.mark.skip(
                reason="property tests need hypothesis (pip extra: test)")(fn)
        return deco

from repro.core import (ClusterManager, FreqPolicy, Log, LogConfig, Node,
                        QuorumError, build_replica_set)

pytestmark = pytest.mark.slow   # spins up replica servers per test

CAP = 1 << 16
STAT_KEYS = ("writes", "bytes_written", "flushes", "lines_flushed", "fences")


def _rs(wq=3, depth=4, adaptive=False, salvage=True, n_backups=2, cap=CAP):
    return build_replica_set(mode="local+remote", capacity=cap,
                             n_backups=n_backups, write_quorum=wq,
                             pipeline_depth=depth, adaptive_depth=adaptive,
                             salvage=salvage)


def _stream(log, pol, n, size=16, tag=0):
    for i in range(n):
        rid, ptr = log.reserve(size)
        data = bytes([(tag + i) & 0xFF]) * size
        if ptr is not None:
            ptr[:] = data
        else:
            log.copy(rid, data)
        log.complete(rid)
        pol.on_complete(log, rid)


def _fail_midwire_then_recover(rs, log, pol, n_before=8, n_after=4):
    """The canonical salvage scenario: W=3 over local+2 backups, node2's
    acks land first, node1 dies mid-wire (fenced) so every in-flight
    round fails, then node1 rejoins and the stream continues."""
    log.append(b"warm" * 4)
    rs.transports[0].inject(delay_s=0.08)   # node1: slow, dies mid-wire
    rs.transports[1].inject(delay_s=0.01)   # node2: acks land first
    _stream(log, pol, n_before)
    rs.kill_backup_midwire("node1", settle_s=0.04)
    assert log.stats()["inflight_rounds"] == 0, "rounds never settled"
    if log.cfg.salvage:
        assert log.stats()["salvage_pending"] > 0, "no salvage stash built"
    rs.recover_backup("node1")
    _stream(log, pol, n_after, tag=0x40)


# --------------------------------------------------------------------- #
# salvage: deltas only, nothing lost, nothing repeated
# --------------------------------------------------------------------- #
def test_salvage_reissues_only_unacked_deltas():
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    _fail_midwire_then_recover(rs, log, pol)
    n2_bytes_before_salvage = rs.servers[1].device.stats.bytes_written
    pol.drain(log)
    st = log.stats()
    total = 1 + 8 + 4
    assert st["durable_lsn"] == total
    assert st["salvage_rounds"] >= 1
    # the headline: re-issue bytes strictly below a full re-issue of the
    # failed rounds (node2 already held every acked range)
    assert 0 < st["reissue_bytes"] < st["full_reissue_bytes"], st
    # every copy converged to the full history
    for s in rs.servers:
        relog = Log.open(s.device, LogConfig(capacity=CAP))
        assert len(list(relog.iter_records())) == total
    # deferred failures were voided by the successful salvage
    log.drain()
    rs.group.drain()
    rs.shutdown()


def test_salvage_skips_already_acked_backup():
    """The healthy backup acked the failed rounds' ranges at first issue:
    salvage must send it nothing for them (only the post-recovery fresh
    rounds land there)."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    # 7 records after the warm lsn 1: leaders 2/4/6/8 cover the whole
    # tail, so the post-recovery force has ONLY salvage work to do
    _fail_midwire_then_recover(rs, log, pol, n_before=7, n_after=0)
    n2_before = rs.servers[1].device.stats.bytes_written
    last = log.next_lsn - 1
    log.force(last, freq=1)                 # leader salvages, no fresh range
    assert log.durable_lsn == last
    assert rs.servers[1].device.stats.bytes_written == n2_before, \
        "salvage re-sent ranges the healthy backup already acked"
    assert log.stats()["reissue_bytes"] > 0
    rs.group.drain()
    rs.shutdown()


def test_salvage_adds_no_primary_hardware_work():
    """Fault + salvage leaves the primary's write-side DeviceStats exactly
    where a fault-free run leaves them: the failed rounds were already
    persisted locally at first issue, and the re-issue reuses the posted
    wire images instead of re-flushing or re-reading anything."""
    runs = {}
    for fault in (False, True):
        rs = _rs()
        log, pol = rs.log, FreqPolicy(2, wait=False)
        if fault:
            _fail_midwire_then_recover(rs, log, pol)
        else:
            log.append(b"warm" * 4)
            _stream(log, pol, 8)
            _stream(log, pol, 4, tag=0x40)
        pol.drain(log)
        assert log.durable_lsn == 13
        runs[fault] = {k: getattr(rs.primary_dev.stats, k)
                       for k in STAT_KEYS}
        rs.group.drain()
        rs.shutdown()
    assert runs[True] == runs[False], runs


def test_salvage_blocking_waiter_raises_then_retry_salvages():
    """A blocking force still surfaces the QuorumError; the app-level
    retry after the backup rejoins goes through salvage, not a full
    re-issue."""
    rs = _rs()
    log = rs.log
    log.append(b"warm")
    rs.transports[1].inject(delay_s=0.01)
    rs.servers[0].fence("node0")            # node1 rejects from the start
    rid, ptr = log.reserve(16)
    ptr[:] = b"x" * 16
    log.complete(rid)
    with pytest.raises(QuorumError):
        log.force(rid, timeout=5.0)
    assert log.durable_lsn == 1
    rs.recover_backup("node1")
    assert log.force(rid, timeout=5.0) == rid
    assert log.stats()["salvage_rounds"] == 1
    assert log.stats()["reissue_bytes"] > 0
    rs.group.drain()
    rs.shutdown()


def test_salvage_retry_budget_surfaces_permanent_failure_on_force():
    """A backup that never rejoins must not let wait=False forces spin
    silently forever: after the bounded salvage retry budget, the
    deferred QuorumError surfaces on force itself (the PR-4 contract),
    not only on drain."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(1, wait=False)
    log.append(b"warm")
    rs.transports[1].inject(delay_s=0.01)
    rs.kill_backup_midwire("node1", settle_s=0.0)   # dies, never rejoins
    raised = 0
    for i in range(16):
        rid, ptr = log.reserve(16)
        ptr[:] = bytes([i]) * 16
        log.complete(rid)
        try:
            pol.on_complete(log, rid)
        except QuorumError:
            raised += 1
    assert raised > 0, "permanent quorum failure never surfaced on force"
    assert log.durable_lsn == 1
    rs.group.drain()
    rs.shutdown()


def test_salvage_unrecovered_backup_still_surfaces_on_drain():
    """No rejoin: salvage retries cannot reach W either — the failure is
    not swallowed, drain raises, and nothing retires past the hole."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    log.append(b"warm")
    rs.transports[1].inject(delay_s=0.01)
    rs.servers[0].fence("node0")
    _stream(log, pol, 4)
    with pytest.raises(QuorumError):
        pol.drain(log)
    assert log.durable_lsn == 1
    rs.group.drain()
    rs.shutdown()


def test_salvage_disabled_matches_salvaged_content():
    """salvage=False keeps the PR-4 full-re-issue behavior; final content
    and watermark are identical to the salvaged run — salvage is an
    optimization, never a semantic change."""
    final = {}
    for salvage in (True, False):
        rs = _rs(salvage=salvage)
        log, pol = rs.log, FreqPolicy(2, wait=False)
        _fail_midwire_then_recover(rs, log, pol, n_after=0)
        if not salvage:
            # PR-4 behavior: the deferred failure surfaces before the
            # full re-issue can proceed; the app absorbs it and retries
            with pytest.raises(QuorumError):
                log.drain(timeout=5.0)
        _stream(log, pol, 4, tag=0x40)
        pol.drain(log)
        relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP))
        final[salvage] = (log.durable_lsn, dict(relog.iter_records()))
        if salvage:
            assert log.stats()["salvage_rounds"] >= 1
        else:
            assert log.stats()["salvage_rounds"] == 0
            assert log.stats()["reissue_bytes"] == 0
        rs.group.drain()
        rs.shutdown()
    assert final[True] == final[False]


def test_fatal_salvage_failure_drops_stash_and_full_reissue_recovers():
    """A salvage round that dies with a NON-salvageable error (fatal lane
    exception, not a quorum/transport failure) must not leave a partial
    stash behind — a later salvage retiring over a never-re-issued gap
    would silently violate durability.  The stash is dropped wholesale
    and the next leader's full fresh re-issue restores every copy."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    _fail_midwire_then_recover(rs, log, pol, n_before=7, n_after=0)
    server = rs.servers[0]
    orig = server.handle_write_imm
    calls = []

    def dying(dst_off, data, primary_id):
        calls.append(dst_off)
        raise ValueError("remote handler bug")     # fatal, not Transport

    server.handle_write_imm = dying
    last = log.next_lsn - 1
    with pytest.raises(ValueError):
        log.force(last, timeout=5.0)               # salvage round dies
    assert log.stats()["salvage_pending"] == 0, \
        "non-salvageable failure left a partial stash"
    server.handle_write_imm = orig
    # with the stash gone, the fence failure's deferred error and each
    # straggler lane's stashed fatal error surface once per call (the
    # PR-4 contract); the app-level retry loop absorbs them, then the
    # full fresh re-issue restores durability
    for _ in range(8):
        try:
            assert log.force(last, timeout=5.0) == last
            break
        except (QuorumError, ValueError):
            continue
    assert log.durable_lsn == last                 # full re-issue worked
    for s in rs.servers:
        relog = Log.open(s.device, LogConfig(capacity=CAP))
        assert len(list(relog.iter_records())) == 8
    rs.group.drain()
    rs.shutdown()


def test_tombstone_generation_blocks_stale_wire_images():
    """A tombstone rewrite bumps the salvage generation; a round posted
    BEFORE the bump that fails AFTER it must not be stashed — re-issuing
    its pre-tombstone wire image could resurrect the record on a backup
    that already applied the tombstone.  cleanup()'s own synchronous
    quorum round FIFO-orders behind in-flight ops on the lanes it needs,
    so the window is a thin race between the lane-thread failure path
    and the tombstone writer — manufactured here by bumping the
    generation directly, exactly as cleanup()/cleanupAll() do."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    log.append(b"warm")
    rs.transports[0].inject(delay_s=0.1)
    rs.transports[1].inject(delay_s=0.01)
    _stream(log, pol, 4)                    # rounds now in flight
    with log._commit_cv:
        log._salvage_gen += 1               # tombstone races the failure
    rs.kill_backup_midwire("node1", settle_s=0.03)   # rounds fail (W=3)
    assert log.stats()["inflight_rounds"] == 0
    assert log.stats()["salvage_pending"] == 0, \
        "pre-tombstone wire images were stashed for re-issue"
    with pytest.raises(QuorumError):
        log.drain(timeout=5.0)              # the failure still surfaces
    rs.group.drain()
    rs.shutdown()


def test_cleanup_drops_salvage_stash():
    """The black-box half of the tombstone guard: tombstoning a record
    inside a stashed (not-yet-durable) range drops the stash wholesale
    (next leader does a fresh full re-issue) — while tombstoning a
    durable record, whose bytes no stash can cover, leaves it alone."""
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    _fail_midwire_then_recover(rs, log, pol, n_before=7, n_after=0)
    assert log.stats()["salvage_pending"] > 0
    log.cleanup(1)                          # durable warm record: no-op
    assert log.stats()["salvage_pending"] > 0
    log.cleanup(3)                          # inside the failed range
    assert log.stats()["salvage_pending"] == 0
    last = log.next_lsn - 1
    # with the stash gone its deferred failure is no longer pending a
    # retry: it surfaces on the next force, then the retry re-issues
    # the whole range fresh
    with pytest.raises(QuorumError):
        log.force(last, timeout=5.0)
    assert log.force(last, timeout=5.0) == last   # full re-issue covers all
    assert log.stats()["reissue_bytes"] == 0      # nothing was salvaged
    rs.group.drain()
    rs.shutdown()


def test_salvage_stash_cap_spills_oldest_and_content_survives():
    """PR-6 satellite: LogConfig.salvage_stash_cap bounds the wire-image
    bytes a long outage can pin.  Spilled lanes lose only their staged
    images (oldest-first) — chain metadata and ack credits survive, the
    re-issue re-snapshots from the primary device, and the final content
    is identical to the uncapped run.  The price is honest: the capped
    run re-sends at least as many wire bytes."""
    final = {}
    stats = {}
    for cap in (None, 1):                   # 1 byte: spill every image
        rs = _rs()
        log, pol = rs.log, FreqPolicy(2, wait=False)
        log.cfg.salvage_stash_cap = cap
        _fail_midwire_then_recover(rs, log, pol, n_before=8, n_after=4)
        pol.drain(log)
        st = log.stats()
        assert st["salvage_stash_cap"] == cap
        relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP))
        final[cap] = (log.durable_lsn, dict(relog.iter_records()))
        stats[cap] = st
        rs.group.drain()
        rs.shutdown()
    assert final[1] == final[None]          # a cap never changes content
    assert stats[None]["salvage_spilled_images"] == 0
    assert stats[1]["salvage_spilled_images"] > 0
    assert stats[1]["salvage_spilled_bytes"] > 0
    assert stats[1]["reissue_bytes"] >= stats[None]["reissue_bytes"]


def test_salvage_stash_bytes_surfaced_and_bounded_by_cap():
    """While a failed round sits stashed, Log.stats() reports the held
    wire-image bytes; with a cap they stay at or below it."""
    cap = 64
    rs = _rs()
    log, pol = rs.log, FreqPolicy(2, wait=False)
    log.cfg.salvage_stash_cap = cap
    log.append(b"warm" * 4)
    rs.transports[0].inject(delay_s=0.08)
    rs.transports[1].inject(delay_s=0.01)
    _stream(log, pol, 8)
    rs.kill_backup_midwire("node1", settle_s=0.04)
    st = log.stats()
    assert st["salvage_pending"] > 0
    assert st["salvage_stash_bytes"] <= cap
    assert st["salvage_spilled_images"] > 0
    rs.recover_backup("node1")
    pol.drain(log)
    assert log.durable_lsn == 9             # nothing lost to the spill
    rs.group.drain()
    rs.shutdown()


def test_failover_abandons_salvage_but_keeps_deferred_error():
    """The failover drain drops the old primary's salvage stash (its wire
    images must never cross the epoch fence) without consuming the
    deferred failure signal."""
    rs = _rs(wq=3)
    nodes = [Node("node0")] + [Node(s.server_id, server=s)
                               for s in rs.servers]
    cm = ClusterManager(nodes)
    cm.attach_log(rs.log)
    rs.log.append(b"warm")
    rs.transports[1].inject(delay_s=0.01)
    rs.servers[0].fence("node0")
    pol = FreqPolicy(2, wait=False)
    _stream(rs.log, pol, 4)
    rs.log.drain(timeout=5.0, surface_errors=False)
    assert rs.log.stats()["salvage_pending"] > 0
    cm.report_failure("node0")
    assert rs.log.stats()["salvage_pending"] == 0
    with pytest.raises(QuorumError):
        rs.log.drain(timeout=5.0)
    rs.shutdown()


# --------------------------------------------------------------------- #
# adaptive pipeline depth
# --------------------------------------------------------------------- #
def test_adaptive_depth_grows_under_backpressure_to_ceiling():
    rs = _rs(wq=2, depth=4, adaptive=True, cap=1 << 20)
    log, pol = rs.log, FreqPolicy(2, wait=False)
    assert log.pipeline_depth == 1          # starts serial
    for _ in range(4):
        log.append(b"w" * 64)
    log.drain()
    for t in rs.transports:
        t.inject(delay_s=0.01)
    _stream(log, pol, 40, size=64)
    pol.drain(log)
    assert log.durable_lsn == 44
    assert log.pipeline_depth == 4          # grew to the ceiling
    assert max(d for _, d in log.depth_trajectory) <= 4
    seqs = [s for s, _ in log.depth_trajectory]
    assert seqs == sorted(seqs)             # trajectory is issue-ordered
    rs.group.drain()
    rs.shutdown()


def test_adaptive_depth_static_config_never_moves():
    rs = _rs(wq=2, depth=4, adaptive=False, cap=1 << 20)
    log, pol = rs.log, FreqPolicy(2, wait=False)
    for t in rs.transports:
        t.inject(delay_s=0.005)
    _stream(log, pol, 24, size=64)
    pol.drain(log)
    assert log.depth_trajectory == [(0, 4)]
    rs.group.drain()
    rs.shutdown()


def test_adaptive_depth_halves_on_failure_and_regrows_after_clean_window():
    rs = _rs(wq=3, depth=4, adaptive=True)
    log, pol = rs.log, FreqPolicy(2, wait=False)
    _fail_midwire_then_recover(rs, log, pol, n_before=12, n_after=0)
    depth_after_failure = log.pipeline_depth
    assert depth_after_failure < 4, log.depth_trajectory
    # clean traffic after the rejoin: the controller must ramp back up
    rs.transports[0].inject(delay_s=0.01)
    rs.transports[1].inject(delay_s=0.01)
    _stream(log, pol, 24, tag=0x40)
    pol.drain(log)
    assert log.durable_lsn == 1 + 12 + 24
    assert log.pipeline_depth == 4, log.depth_trajectory
    depths = [d for _, d in log.depth_trajectory]
    assert max(depths) <= 4 and min(depths) >= 1
    rs.group.drain()
    rs.shutdown()


def test_adaptive_depth_halves_on_slot_timeout():
    rs = _rs(wq=2, depth=2, adaptive=True, n_backups=1)
    log = rs.log
    log.append(b"w")
    # grow to 2 with clean overlapped traffic
    rs.transports[0].inject(delay_s=0.05)
    pol = FreqPolicy(1, wait=False)
    _stream(log, pol, 2)
    assert log.pipeline_depth == 2
    rs.transports[0].inject(delay_s=0.5)    # now rounds crawl
    _stream(log, pol, 2, tag=8)             # fill both slots
    rid, ptr = log.reserve(16)
    ptr[:] = b"t" * 16
    log.complete(rid)
    with pytest.raises(Exception):
        log.force(rid, timeout=0.05)        # no slot in time
    assert log.pipeline_depth == 1          # halved by the timeout
    log.drain(timeout=5.0)
    assert log.force(rid) == rid
    rs.group.drain()
    rs.shutdown()


def test_effective_vulnerability_bound_tracks_live_depth():
    rs = _rs(wq=2, depth=4, adaptive=True, cap=1 << 20)
    log = rs.log
    log.cfg.max_threads = 1
    pol = FreqPolicy(4, wait=False)
    # ceiling bound is static; the tightened effective bound (per-round
    # span accounting) is one F×T window while the pipeline is empty
    assert pol.vulnerability_bound(log) == 4 * (4 + 1)
    assert pol.effective_vulnerability_bound(log) == 4
    for t in rs.transports:
        t.inject(delay_s=0.01)
    _stream(log, pol, 32, size=32)
    pol.drain(log)
    assert log.pipeline_depth == 4
    # drained: no in-flight span, so the effective bound collapses back
    # to one window — and never exceeds the static ceiling promise
    assert pol.effective_vulnerability_bound(log) == 4
    assert pol.effective_vulnerability_bound(log) < \
        pol.vulnerability_bound(log)
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# property tests: controller + salvage invariants (hypothesis-guarded,
# with deterministic fallback sweeps)
# --------------------------------------------------------------------- #
def _controller_invariants(seed: int) -> None:
    """One randomized run: depth never exceeds the ceiling, durable_lsn
    stays a gapless prefix under any grow/shrink schedule, and the final
    recovered contents match what was appended."""
    import numpy as np
    rng = np.random.default_rng(seed)
    ceiling = int(rng.integers(1, 6))
    wq = int(rng.integers(2, 4))
    rs = _rs(wq=wq, depth=ceiling, adaptive=True)
    log, pol = rs.log, FreqPolicy(int(rng.integers(1, 4)), wait=False)
    written = {}
    n = int(rng.integers(6, 20))
    fail_at = int(rng.integers(2, n)) if rng.random() < 0.5 and wq == 3 \
        else None
    rs.transports[1].inject(delay_s=0.002)
    try:
        for i in range(n):
            if fail_at is not None and i == fail_at:
                rs.kill_backup_midwire("node1", settle_s=0.0)
                rs.recover_backup("node1")
            rid, ptr = log.reserve(24)
            data = bytes([(seed + i) & 0xFF]) * 24
            ptr[:] = data
            written[rid] = data
            log.complete(rid)
            pol.on_complete(log, rid)
            st = log.stats()
            assert 1 <= st["pipeline_depth"] <= ceiling
            assert st["durable_lsn"] <= st["complete_upto"]
        pol.drain(log)
        assert log.durable_lsn == n
        assert all(1 <= d <= ceiling for _, d in log.depth_trajectory)
        relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP))
        got = dict(relog.iter_records())
        assert got == written        # gapless, intact, nothing lost
    finally:
        rs.group.drain()
        rs.shutdown()


def test_controller_invariants_deterministic_sweep():
    for seed in range(8):
        _controller_invariants(seed)


@settings(max_examples=15, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_controller_invariants_property(seed):
    _controller_invariants(seed)


def _salvage_equivalence(seed: int) -> None:
    """Salvage vs full re-issue: identical final durable watermark and
    record contents under the same fault schedule; the salvaged run never
    re-sends more than the full re-issue counterfactual."""
    import numpy as np
    final = {}
    for salvage in (True, False):
        rng = np.random.default_rng(seed)
        rs = _rs(salvage=salvage)
        log, pol = rs.log, FreqPolicy(2, wait=False)
        n = int(rng.integers(6, 16))
        fail_at = int(rng.integers(1, n))
        rs.transports[0].inject(delay_s=0.06)
        rs.transports[1].inject(delay_s=0.002)
        try:
            for i in range(n):
                if i == fail_at:
                    rs.kill_backup_midwire("node1", settle_s=0.01)
                    rs.recover_backup("node1")
                rid, ptr = log.reserve(24)
                ptr[:] = bytes([(seed + i) & 0xFF]) * 24
                log.complete(rid)
                try:
                    pol.on_complete(log, rid)
                except QuorumError:
                    # full-re-issue arm only: the deferred failure
                    # surfaces on the next force; the app retries
                    assert not salvage
                    pol.on_complete(log, rid)
            try:
                pol.drain(log)
            except QuorumError:
                assert not salvage
                pol.drain(log)
            relog = Log.open(rs.primary_dev, LogConfig(capacity=CAP))
            final[salvage] = (log.durable_lsn, dict(relog.iter_records()))
            if salvage:
                assert log.stats()["reissue_bytes"] <= \
                    log.stats()["full_reissue_bytes"]
        finally:
            rs.group.drain()
            rs.shutdown()
    assert final[True] == final[False]


def test_salvage_equivalence_deterministic_sweep():
    for seed in range(6):
        _salvage_equivalence(seed)


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_salvage_equivalence_property(seed):
    _salvage_equivalence(seed)
