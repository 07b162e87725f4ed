"""Pipelined force engine (DESIGN.md §8): round overlap, in-order
watermark retirement, failure semantics, non-blocking leader handoff,
and pipeline drain."""

import threading
import time

import pytest

from repro.core import (ClusterManager, FreqPolicy, Log, LogConfig, LogError,
                        Node, PMEMDevice, QuorumError, build_replica_set)
from repro.core.log import _PipeRound
from repro.core.replication import device_size

pytestmark = pytest.mark.slow   # spins up replica servers per test

CAP = 1 << 16


def _pipelined_rs(depth, n_backups=2, write_quorum=2):
    return build_replica_set(mode="local+remote", capacity=CAP,
                             n_backups=n_backups, write_quorum=write_quorum,
                             pipeline_depth=depth)


def _stream(log, pol, n, size=64):
    for _ in range(n):
        rid, ptr = log.reserve(size)
        ptr[:] = b"x" * size
        log.complete(rid)
        pol.on_complete(log, rid)


# --------------------------------------------------------------------- #
# overlap + in-order retirement
# --------------------------------------------------------------------- #
def test_pipeline_depth_overlaps_wire_rounds():
    """Depth D must overlap durability rounds on the wire: wall-clock of
    a non-blocking force stream over an injected RTT drops well below
    the serial (depth-1) run."""
    walls = {}
    for depth in (1, 4):
        rs = _pipelined_rs(depth)
        pol = FreqPolicy(4, wait=False)
        _stream(rs.log, pol, 8)            # warm the whole path, undelayed
        pol.drain(rs.log)
        for t in rs.transports:
            t.inject(delay_s=0.01)
        t0 = time.perf_counter()
        _stream(rs.log, pol, 48)           # 12 durability rounds
        pol.drain(rs.log)
        walls[depth] = time.perf_counter() - t0
        assert rs.log.durable_lsn == 56
        rs.group.drain()
        rs.shutdown()
    # serial ≈ 12 RTTs, depth-4 ≈ 3-4 RTTs; 0.7 leaves headroom for a
    # noisy scheduler without masking a lost overlap
    assert walls[4] < walls[1] * 0.7, walls


def test_concurrent_writers_gapless_watermark():
    """durable_lsn only ever advances over a gapless prefix, even with
    concurrent writers feeding a depth-4 pipeline; every backup ends up
    holding the full history."""
    rs = _pipelined_rs(4)
    log = rs.log
    pol = FreqPolicy(2, wait=False)
    errors = []

    def worker():
        try:
            for _ in range(30):
                rid, ptr = log.reserve(16)
                ptr[:] = b"c" * 16
                log.complete(rid)
                pol.on_complete(log, rid)
                d = log.durable_lsn
                c = log.completed_lsn          # read after d: c >= c@d
                assert d <= c, f"watermark {d} ahead of complete {c}"
        except Exception as e:                 # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    pol.drain(log)
    assert not errors
    assert log.durable_lsn == 120
    assert log.stats()["inflight_rounds"] == 0
    for s in rs.servers:
        relog = Log.open(s.device, LogConfig(capacity=CAP))
        assert len(list(relog.iter_records())) == 120
    rs.shutdown()


def test_wait_false_returns_before_quorum():
    """Non-blocking leader handoff: force(wait=False) returns after the
    doorbell post, not after the W-th ack."""
    rs = _pipelined_rs(4, n_backups=1, write_quorum=2)
    rs.log.append(b"w")
    rs.log.drain()
    rs.transports[0].inject(delay_s=0.2)
    rid, ptr = rs.log.reserve(8)
    ptr[:] = b"q" * 8
    rs.log.complete(rid)
    t0 = time.perf_counter()
    rs.log.force(rid, wait=False)
    assert time.perf_counter() - t0 < 0.1, "handoff blocked on the wire"
    assert rs.log.durable_lsn < rid
    rs.log.drain(timeout=5.0)
    assert rs.log.durable_lsn == rid
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# failure paths
# --------------------------------------------------------------------- #
def test_force_exception_resets_pipeline_and_unblocks_later_forces():
    """An exception inside a force round (here: the local flush dies)
    resets the pipeline state — no in-flight round, issue watermark
    rolled back — and later forces succeed without re-raising."""
    dev = PMEMDevice(device_size(CAP))
    log = Log.create(dev, LogConfig(capacity=CAP))
    rid, ptr = log.reserve(8)
    ptr[:] = b"a" * 8
    log.complete(rid)
    orig = dev.persist
    dev.persist = lambda off, n: (_ for _ in ()).throw(
        RuntimeError("flush died"))
    with pytest.raises(RuntimeError):
        log.force(rid)
    dev.persist = orig
    assert log.stats()["inflight_rounds"] == 0
    assert log.pipeline_free
    assert log.force(rid) == rid           # no deferred re-raise, no wedge
    assert log.durable_lsn == rid


def test_force_timeout_on_incomplete_record_does_not_wedge():
    dev = PMEMDevice(device_size(CAP))
    log = Log.create(dev, LogConfig(capacity=CAP))
    rid, ptr = log.reserve(8)
    with pytest.raises(LogError):
        log.force(rid, timeout=0.05)       # never completed: times out
    ptr[:] = b"b" * 8
    log.complete(rid)
    assert log.force(rid) == rid


def test_force_timeout_on_stuck_round_does_not_wedge_later_forces():
    rs = _pipelined_rs(2, n_backups=1, write_quorum=2)
    rs.log.append(b"w")
    rs.transports[0].inject(delay_s=0.4)
    rid, ptr = rs.log.reserve(8)
    ptr[:] = b"s" * 8
    rs.log.complete(rid)
    with pytest.raises(LogError):
        rs.log.force(rid, timeout=0.05)    # round still on the wire
    rid2, p2 = rs.log.reserve(8)
    p2[:] = b"t" * 8
    rs.log.complete(rid2)
    # once the wire settles, the pipeline keeps retiring in order
    assert rs.log.force(rid2, timeout=5.0) == rid2
    rs.log.drain(timeout=5.0)
    rs.group.drain()
    rs.shutdown()


def test_pipelined_quorum_error_propagates_to_all_covered_waiters():
    """Two rounds in flight; the head round's quorum fails (old primary
    gets fenced mid-wire) — BOTH waiters must raise QuorumError: a hole
    can never be skipped, so the failure of round N fails round N+1."""
    rs = _pipelined_rs(2, n_backups=2, write_quorum=3)
    rs.log.append(b"w")
    rs.transports[0].inject(delay_s=0.3)   # node1's wire is slow
    results = []

    def forcer(rid):
        try:
            rs.log.force(rid, timeout=5.0)
            results.append(None)
        except Exception as e:
            results.append(e)

    threads = []
    for i in range(2):
        rid, ptr = rs.log.reserve(8)
        ptr[:] = bytes([i]) * 8
        rs.log.complete(rid)
        th = threading.Thread(target=forcer, args=(rid,))
        th.start()
        threads.append(th)
        deadline = time.time() + 2.0
        while rs.log.stats()["issue_lsn"] < rid and time.time() < deadline:
            time.sleep(0.005)
        assert rs.log.stats()["issue_lsn"] >= rid, "round never issued"
    rs.servers[0].fence("node0")           # node1 now rejects the writes
    for th in threads:
        th.join(timeout=10.0)
    assert len(results) == 2
    assert all(isinstance(r, QuorumError) for r in results), results
    # pipeline reset: nothing in flight, watermark never skipped the hole
    assert rs.log.stats()["inflight_rounds"] == 0
    assert rs.log.durable_lsn == 1
    rs.group.drain()
    rs.shutdown()


def test_wait_false_round_failure_surfaces_on_drain():
    """A non-blocking round that fails with no covering waiter defers
    its QuorumError to drain (kv.flush) instead of dropping it."""
    rs = _pipelined_rs(2, n_backups=2, write_quorum=3)
    rs.log.append(b"w")
    rs.fail_backup("node1")                # W=3 now unreachable
    rid, ptr = rs.log.reserve(8)
    ptr[:] = b"z" * 8
    rs.log.complete(rid)
    rs.log.force(rid, wait=False)
    with pytest.raises(QuorumError):
        rs.log.drain(timeout=5.0)
    assert rs.log.stats()["inflight_rounds"] == 0
    assert rs.log.durable_lsn == 1         # failed round never retired
    rs.shutdown()


def test_wait_false_window_stays_within_pipelined_bound():
    """The F×T bound does not hold under the non-blocking handoff (up to
    depth issued-but-unretired rounds extend the window); the policy
    must report the pipelined bound (depth+1)×F×T and the observed
    window must respect it."""
    rs = _pipelined_rs(4, n_backups=1, write_quorum=2)
    log = rs.log
    log.cfg.max_threads = 1                # single writer: T = 1
    rs.transports[0].inject(delay_s=0.05)  # keep rounds in flight
    pol = FreqPolicy(4, wait=False)
    assert pol.vulnerability_bound(log) == 4 * 1 * (4 + 1)
    worst = 0
    for _ in range(32):
        rid, ptr = log.reserve(8)
        ptr[:] = b"v" * 8
        log.complete(rid)
        pol.on_complete(log, rid)
        worst = max(worst, log.vulnerability_window())
    assert worst <= pol.vulnerability_bound(log), \
        f"window {worst} exceeds pipelined bound"
    assert worst > 4, "pipeline never extended the window (test inert)"
    pol.drain(log)
    rs.group.drain()
    rs.shutdown()


def test_force_on_durable_lsn_does_not_block_behind_issue_lock():
    """A force whose LSN is already durable must return immediately even
    while a slot-waiting leader holds the issue lock across a wire
    round (fast path ahead of _issue_lock)."""
    rs = _pipelined_rs(1, n_backups=1, write_quorum=2)
    log = rs.log
    log.append(b"a")                       # lsn 1 durable
    rs.transports[0].inject(delay_s=0.3)
    rid2, p2 = log.reserve(8)
    p2[:] = b"b" * 8
    log.complete(rid2)
    log.force(rid2, wait=False)            # round 2 on the wire
    rid3, p3 = log.reserve(8)
    p3[:] = b"c" * 8
    log.complete(rid3)
    blocker = threading.Thread(target=log.force, args=(rid3,))
    blocker.start()                        # waits for a depth-1 slot
    time.sleep(0.05)                       # let it grab _issue_lock
    t0 = time.perf_counter()
    assert log.force(1) >= 1               # already durable: instant
    assert time.perf_counter() - t0 < 0.1, \
        "durable-LSN force queued behind the issue lock"
    blocker.join(timeout=5.0)
    rs.log.drain(timeout=5.0)
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# failover drains the pipeline before the epoch fence
# --------------------------------------------------------------------- #
def test_cluster_failover_drains_pipeline_before_fencing():
    rs = _pipelined_rs(4)
    nodes = [Node("node0")] + [Node(s.server_id, server=s)
                               for s in rs.servers]
    cm = ClusterManager(nodes)
    cm.attach_log(rs.log)
    for t in rs.transports:
        t.inject(delay_s=0.1)
    pol = FreqPolicy(2, wait=False)
    _stream(rs.log, pol, 8, size=8)
    # rounds are in flight; the failover must settle them BEFORE backups
    # fence the old primary, so no round straddles the epoch change
    assert cm.report_failure("node0") == "node1"
    assert rs.log.stats()["inflight_rounds"] == 0
    assert rs.log.durable_lsn == 8
    rs.group.drain()
    rs.shutdown()


def test_cluster_drain_preserves_deferred_round_errors():
    """The failover drain settles the pipeline with surface_errors=False:
    a deferred wait=False QuorumError must still raise on the log's own
    next drain, not vanish into report_failure's best-effort except."""
    rs = _pipelined_rs(2, n_backups=2, write_quorum=3)
    nodes = [Node("node0")] + [Node(s.server_id, server=s)
                               for s in rs.servers]
    cm = ClusterManager(nodes)
    cm.attach_log(rs.log)
    rs.log.append(b"w")
    rs.fail_backup("node1")                # W=3 unreachable from now on
    rid, ptr = rs.log.reserve(8)
    ptr[:] = b"z" * 8
    rs.log.complete(rid)
    rs.log.force(rid, wait=False)          # fails with no covering waiter
    rs.log.drain(timeout=5.0, surface_errors=False)   # round settled
    cm.report_failure("node0")             # failover drain runs here
    with pytest.raises(QuorumError):       # ...but the signal survived
        rs.log.drain(timeout=5.0)
    rs.shutdown()


# --------------------------------------------------------------------- #
# deferred-error backlog coalescing (DESIGN.md §11 satellite)
# --------------------------------------------------------------------- #
def test_deferred_error_storm_coalesces_into_one_drain():
    """A storm of failed wait=False rounds queues one error per round;
    they must surface in ONE drain — the oldest raises with the rest of
    the backlog riding on exc.pipe_backlog — and the next drain is
    clean.  (Previously each drain popped a single error, so apps
    needed a bounded retry loop to converge.)"""
    rs = _pipelined_rs(4, n_backups=2, write_quorum=3)
    log = rs.log
    log.append(b"w")                        # lsn 1 durable
    rs.fail_backup("node1")                 # W=3 unreachable from now on

    def settle(deadline=5.0):
        end = time.monotonic() + deadline
        while log.stats()["inflight_rounds"] and time.monotonic() < end:
            time.sleep(0.002)

    for _ in range(3):                      # three sequential failed rounds
        rid, ptr = log.reserve(8)
        ptr[:] = b"z" * 8
        log.complete(rid)
        log.force(rid, wait=False)
        settle()
    backlog = log.stats()["deferred_errors"]
    assert backlog >= 2, "storm never accumulated a backlog (test inert)"
    with pytest.raises(QuorumError) as ei:
        log.drain(timeout=5.0)
    # the whole backlog rode out on the single raise
    assert len(ei.value.pipe_backlog) == backlog - 1
    assert log.stats()["deferred_errors"] == 0
    log.drain(timeout=5.0)                  # second drain MUST be clean
    assert log.durable_lsn == 1             # failed rounds never retired
    rs.shutdown()


# --------------------------------------------------------------------- #
# tightened vulnerability bound: per-round-span accounting (satellite)
# --------------------------------------------------------------------- #
def test_effective_bound_per_round_span_accounting_at_depth1():
    """Pin both formulas at depth 1.  The static promise stays
    (depth+1)×F×T for the non-blocking handoff; the effective bound is
    one policy window plus the MEASURED in-flight span, capped by the
    static formula — so an idle pipeline reports F×T, a single live
    round reports F×T + its span, and wait=True keeps the classic
    equalities."""
    rs = _pipelined_rs(1, n_backups=1, write_quorum=2)
    log = rs.log
    log.cfg.max_threads = 1                 # T = 1

    # wait=True, depth 1: the serial engine — both formulas are F×T
    pol_w = FreqPolicy(4, wait=True)
    assert pol_w.vulnerability_bound(log) == 4
    assert pol_w.effective_vulnerability_bound(log) == 4

    # wait=False: the static bound doubles, the effective bound does not
    pol = FreqPolicy(4, wait=False)
    assert pol.vulnerability_bound(log) == 4 * (1 + 1)
    assert pol.effective_vulnerability_bound(log) == 4
    assert log.inflight_span() == 0

    # park one small round in flight: effective = window + live span,
    # strictly tighter than the static (depth+1) multiplication
    rs.transports[0].inject(delay_s=0.08)
    rid, ptr = log.reserve(8)
    ptr[:] = b"s" * 8
    log.complete(rid)
    log.force(rid, wait=False)
    assert log.inflight_span() == 1
    assert pol.effective_vulnerability_bound(log) == 4 + 1
    assert pol.effective_vulnerability_bound(log) < \
        pol.vulnerability_bound(log)
    log.drain(timeout=5.0)
    assert pol.effective_vulnerability_bound(log) == 4
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# durable watermark and ack times across salvage; ack-time attribution
# --------------------------------------------------------------------- #
def test_salvage_round_keeps_watermark_and_ack_times_monotone():
    """A mid-pipeline backup death fails in-flight rounds; the salvage
    re-issue retires them, the durable watermark never moves back, and
    every record's ack time is its covering round's retirement, in LSN
    order."""
    rs = build_replica_set(mode="local+remote", capacity=CAP,
                           n_backups=2, write_quorum=3, pipeline_depth=4)
    pol = FreqPolicy(4, wait=False)
    for _ in range(8):
        rs.log.append(b"v" * 64)
    rs.log.drain()
    seen = [rs.log.durable_lsn]
    rs.transports[0].inject(delay_s=0.03)
    rs.transports[1].inject(delay_s=0.002)
    for i in range(32):
        if i == 16:
            rs.kill_backup_midwire("node1", settle_s=0.016)
            rs.recover_backup("node1")
        rid, ptr = rs.log.reserve(64)
        ptr[:] = b"v" * 64
        rs.log.complete(rid)
        pol.on_complete(rs.log, rid)
        seen.append(rs.log.durable_lsn)
    pol.drain(rs.log)
    st = rs.log.stats()
    times = rs.log.durable_ack_times(list(range(1, 41)))
    rs.group.drain()
    rs.shutdown()
    assert st["salvage_rounds"] >= 1             # the scenario really fired
    assert seen == sorted(seen)                  # watermark monotone
    assert st["durable_lsn"] == 40
    assert None not in times
    assert times == sorted(times)                # retirement is head-first


def test_ack_time_is_the_covering_round_retirement():
    """Single-threaded sync stream: each record rides its own round, so
    its ack time is that round's retirement, inside its own append; the
    members of one batch ride one round and share its stamp."""
    rs = build_replica_set(mode="local+remote", capacity=CAP,
                           n_backups=2, write_quorum=3, pipeline_depth=1)
    rids, stamps = [], []
    for i in range(12):
        t0 = time.monotonic()
        rid = rs.log.append(bytes([i]) * 80)
        t1 = time.monotonic()
        at = rs.log.durable_ack_time(rid)
        assert at is not None and t0 <= at <= t1
        rids.append(rid)
        stamps.append(at)
    assert stamps == sorted(stamps) and len(set(stamps)) == len(stamps)
    assert rs.log.durable_ack_times(rids + rids) == stamps + stamps
    lsns = rs.log.append_batch([b"b" * 80] * 4)
    batch = rs.log.durable_ack_times(lsns)
    assert len(set(batch)) == 1 and batch[0] > stamps[-1]
    assert rs.log.stats()["rounds_retired"] == 13
    rs.group.drain()
    rs.shutdown()


def test_ack_time_history_boundaries():
    rs = build_replica_set(mode="local+remote", capacity=CAP,
                           n_backups=2, write_quorum=3, pipeline_depth=1)
    rid = rs.log.append(b"a" * 64)
    assert rs.log.durable_ack_time(rid + 1) is None     # not durable yet
    at = rs.log.durable_ack_time(rid)
    assert at is not None
    assert rs.log.durable_ack_times([rid, rid + 1]) == [at, None]
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# pump exception discipline
# --------------------------------------------------------------------- #
class _KIHandle:
    """Settled handle whose first wait raises KeyboardInterrupt — the
    settling thread being interrupted, not the round failing."""

    def __init__(self):
        self._raised = False

    def done(self):
        return True

    def wait(self, timeout=None):
        if not self._raised:
            self._raised = True
            raise KeyboardInterrupt()


def test_pump_lets_keyboard_interrupt_propagate_without_failing_round():
    """An operator Ctrl-C on the settling thread must propagate from
    _pipe_pump, not be converted into a permanently failed round, and
    leave the round retire-able."""
    dev = PMEMDevice(device_size(CAP))
    log = Log(dev, LogConfig(capacity=CAP, pipeline_depth=2))
    rid, ptr = log.reserve(64)
    ptr[:] = b"k" * 64
    log.complete(rid)
    entry = _PipeRound(rid, 0, 128, gen=log._salvage_gen,
                       issued_at=time.monotonic())
    entry.handle = _KIHandle()
    with log._commit_cv:
        log._inflight.append(entry)
    with pytest.raises(KeyboardInterrupt):
        log._pipe_pump()
    # the interrupt did NOT poison the pipeline
    assert entry.error is None
    assert log._inflight and log._inflight[0] is entry
    # the next pump retires the round cleanly
    log._pipe_pump()
    assert not log._inflight
    assert log.durable_lsn == rid
    assert log.stats()["rounds_retired"] == 1
