"""Multi-producer group-commit ingestion engine (DESIGN.md §10).

Correctness: concurrent producers coalesce into shared batched waves,
every ticket resolves to a durable LSN or an error (never both, never
neither), and the recovered log holds exactly the acked multiset with
gapless LSNs.

Admission control: the bounded front door really bounds producer-visible
memory, and the three modes fail distinctly — block waits, fail raises
IngestQueueFull, shed raises IngestShedError after its deadline.

Accounting: per-record latency is the submit→durable-ack interval
stamped from the covering round's retirement (Log.durable_ack_time),
append/append_batch followed by durable_ack_time(s) report honest
per-record ack times, and the ack-rate (BDP) grow signal follows a
pinned trajectory on a deterministic schedule.
"""

import threading
import time
from collections import deque

import pytest

from repro.core import (AckRateEstimator, FreqPolicy, IngestClosedError,
                        IngestConfig, IngestEngine, IngestError,
                        IngestQueueFull, IngestShedError, Log, LogConfig,
                        PMEMDevice, SyncPolicy, build_replica_set,
                        device_size, latency_percentiles)

pytestmark = pytest.mark.slow   # engine threads + replica servers per test

CAP = 1 << 18


def _local_log(cap=CAP, mode="fast", **cfg):
    dev = PMEMDevice(device_size(cap), mode=mode)
    return dev, Log.create(dev, LogConfig(capacity=cap, **cfg))


def _payloads(tid, n, size=24):
    return [f"p{tid:02d}-{i:04d}".encode().ljust(size, b".")
            for i in range(n)]


# --------------------------------------------------------------------- #
# multi-producer correctness
# --------------------------------------------------------------------- #
def test_concurrent_producers_all_acked_and_recovered():
    dev, log = _local_log(pipeline_depth=4)
    eng = IngestEngine(log, IngestConfig())
    n_threads, per = 8, 50
    tickets = [[] for _ in range(n_threads)]
    # all producers alive at once: a thread that starts after another
    # has exited may reuse its ident and pass for the same producer
    start = threading.Barrier(n_threads)

    def producer(tid):
        start.wait(timeout=30)
        for p in _payloads(tid, per):
            tickets[tid].append(eng.append(p))
        for t in tickets[tid]:
            t.wait(timeout=30)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    st = eng.stats()
    assert st["acked"] == st["submitted"] == n_threads * per
    assert st["failed"] == 0
    # coalescing actually happened: strictly fewer waves than records
    assert 0 < st["waves"] < n_threads * per
    eng.close()

    # every ticket's LSN is unique; recovery sees the exact multiset
    lsns = [t.lsn for tid in range(n_threads) for t in tickets[tid]]
    assert len(set(lsns)) == len(lsns)
    relog = Log.open(dev, LogConfig(capacity=CAP))
    recovered = {lsn: bytes(p) for lsn, p in relog.iter_records()}
    assert sorted(recovered) == list(range(1, len(lsns) + 1))   # gapless
    for tid in range(n_threads):
        for t, p in zip(tickets[tid], _payloads(tid, per)):
            assert recovered[t.lsn] == p


def test_ack_times_are_record_level_and_ordered():
    _, log = _local_log()
    eng = IngestEngine(log, IngestConfig())
    ts = [eng.append(b"x" * 32) for _ in range(64)]
    eng.drain()
    for t in ts:
        assert t.done and t.error is None
        assert t.t_ack is not None and t.t_ack >= t.t_submit
        assert t.latency_s >= 0.0
        # the stamp is the covering round's retirement wall time
        assert t.t_ack == log.durable_ack_time(t.lsn)
    by_lsn = sorted(ts, key=lambda t: t.lsn)
    acks = [t.t_ack for t in by_lsn]
    assert acks == sorted(acks)     # retirement is in-order, so are acks
    eng.close()


def test_large_wave_slices_across_pipeline_slots():
    # a slow wire and a single slot pin the collector behind round 1, so
    # the rest of the stream accumulates into one big wave — which must
    # then go out as many slice_bytes-sized forces, not one monolith
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2, pipeline_depth=1)
    rs.transports[0].inject(delay_s=0.05)
    eng = IngestEngine(rs.log, IngestConfig(slice_bytes=256))
    ts = [eng.append(b"s" * 100) for _ in range(40)]
    eng.drain()
    st = eng.stats()
    assert st["max_wave_records"] > 8           # coalescing happened
    assert st["forced_slices"] > st["waves"]    # waves really were sliced
    assert all(t.error is None for t in ts)
    eng.close()
    rs.shutdown()


# --------------------------------------------------------------------- #
# admission control
# --------------------------------------------------------------------- #
def _congested_engine(admission, queue_records=4, **kw):
    """A replica set whose wire crawls, so the queue actually fills."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2)
    rs.transports[0].inject(delay_s=0.2)
    cfg = IngestConfig(queue_records=queue_records, admission=admission,
                       flush_records=queue_records, **kw)
    return rs, IngestEngine(rs.log, cfg)


def test_fail_fast_raises_queue_full():
    rs, eng = _congested_engine("fail")
    with pytest.raises(IngestQueueFull):
        for _ in range(64):
            eng.append(b"f" * 16)
    assert eng.stats()["rejected"] >= 1
    eng.close()
    rs.shutdown()


def test_shed_mode_raises_distinct_error_after_deadline():
    rs, eng = _congested_engine("shed", shed_deadline_s=0.01)
    t0 = time.monotonic()
    with pytest.raises(IngestShedError) as ei:
        for _ in range(64):
            eng.append(b"s" * 16)
    assert time.monotonic() - t0 >= 0.01        # really waited the deadline
    assert not isinstance(ei.value, IngestQueueFull)
    assert eng.stats()["shed"] >= 1
    eng.close()
    rs.shutdown()


def test_block_mode_bounds_producer_visible_memory():
    b_records, b_bytes = 8, 8 * 64
    rs, eng = _congested_engine("block", queue_records=b_records,
                                queue_bytes=b_bytes)
    done = []

    def producer():
        for _ in range(24):
            eng.append(b"b" * 64, timeout=30)
        done.append(True)

    threads = [threading.Thread(target=producer) for _ in range(4)]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=60)
    assert len(done) == 4 and not any(th.is_alive() for th in threads)
    eng.drain()
    st = eng.stats()
    assert st["acked"] == 4 * 24
    # O(B): admission never let the queue exceed its bounds
    assert st["peak_queue_records"] <= b_records
    assert st["peak_queue_bytes"] <= b_bytes
    eng.close()
    rs.shutdown()


def test_oversized_record_admitted_alone_not_deadlocked():
    _, log = _local_log()
    eng = IngestEngine(log, IngestConfig(queue_bytes=64))
    t = eng.append(b"o" * 256)          # larger than the whole byte budget
    assert t.wait(timeout=10) >= 0
    eng.close()


def test_block_admission_timeout_raises():
    rs, eng = _congested_engine("block")
    with pytest.raises(IngestError):
        for _ in range(64):
            eng.append(b"t" * 16, timeout=0.01)
    eng.close()
    rs.shutdown()


# --------------------------------------------------------------------- #
# drain / close: nobody is ever stranded
# --------------------------------------------------------------------- #
def test_drain_fails_every_ticket_on_permanent_quorum_loss():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2)
    eng = IngestEngine(rs.log, IngestConfig(),
                       policy=FreqPolicy(64, wait=False))
    ts = [eng.append(b"q" * 16) for _ in range(8)]    # no leader yet
    rs.fail_backup("node1")                           # quorum is gone
    with pytest.raises(Exception):
        eng.drain(timeout=30)
    for t in ts:
        assert t.done                                 # acked or failed —
        if t.error is None:                           # never stranded
            assert t.lsn <= rs.log.durable_lsn
        else:
            with pytest.raises(Exception):
                t.wait(timeout=1)
    eng.close()
    rs.shutdown()


def test_close_rejects_new_appends_and_is_idempotent():
    _, log = _local_log()
    eng = IngestEngine(log)
    eng.append(b"c" * 8)
    eng.close()
    eng.close()
    with pytest.raises(IngestClosedError):
        eng.append(b"late")


def test_ticket_wait_timeout_raises_ingest_error():
    rs, eng = _congested_engine("block", queue_records=64)
    t = eng.append(b"w" * 16)
    with pytest.raises(IngestError):
        t.wait(timeout=0.01)
    eng.close()          # settles the wire; the ticket resolves here
    rs.shutdown()


# --------------------------------------------------------------------- #
# per-record latency attribution (durable_ack_time / durable_ack_times)
# --------------------------------------------------------------------- #
def test_append_timed_per_record_reports_ack_time():
    _, log = _local_log()
    t0 = time.monotonic()
    rid = log.append(b"a" * 32)
    ack = log.durable_ack_time(rid)
    assert rid == 1
    assert ack is not None and t0 <= ack <= time.monotonic()


def test_append_batch_timed_per_record_acks_every_member():
    _, log = _local_log()
    lsns = log.append_batch([b"b" * 32] * 10)
    acks = log.durable_ack_times(lsns)
    assert len(acks) == len(lsns) == 10
    assert all(a is not None for a in acks)
    assert acks == sorted(acks)
    # one force covered the whole batch: one retirement stamp for all
    assert len(set(acks)) == 1


def test_unforced_records_have_no_ack_time():
    _, log = _local_log()
    lsns = log.append_batch([b"u" * 16] * 8, freq=64)
    assert log.durable_ack_time(lsns[-1]) is None   # never forced
    log.force(lsns[-1])
    assert log.durable_ack_time(lsns[-1]) is not None


def test_latency_percentiles_nearest_rank():
    samples = [i / 1000.0 for i in range(1, 101)]
    pct = latency_percentiles(samples)
    assert pct["p50"] == 0.050
    assert pct["p99"] == 0.099
    assert pct["p999"] == 0.100
    nan = latency_percentiles([])
    assert all(v != v for v in nan.values())        # NaN on empty


# --------------------------------------------------------------------- #
# ack-rate (BDP) estimator: pinned trajectory on a deterministic schedule
# --------------------------------------------------------------------- #
def test_ack_rate_estimator_pinned_trajectory():
    """Power-of-two timestamps (n/1024 s) keep every EMA float-exact, so
    the BDP sequence is pinned, not approximated."""
    est = AckRateEstimator(alpha=0.5)
    assert est.bdp_rounds() is None                 # bootstrap
    assert est.supports_growth(4)                   # …never vetoes

    u = 1.0 / 1024.0
    for i in range(4):                              # arrivals 1u apart
        est.observe_arrival(i * u)
    assert est.gap_ema == u
    assert est.bdp_rounds() is None                 # no retirement yet

    est.observe_retire(now=11 * u, issued_at=3 * u)  # latency 8u
    assert est.lat_ema == 8 * u
    assert est.bdp_rounds() == 8                    # ceil(8u / 1u)
    assert est.supports_growth(4)                   # 8 >= 4: grow ok
    assert est.supports_growth(8)
    assert not est.supports_growth(9)

    # demand slows to one leader per 8u: gap EMA walks 1 → 4.5 → 6.25
    # → 7.125 (exact halvings), BDP collapses to 2 and stays there
    pinned = [2, 2, 2]
    for k, want in enumerate(pinned, start=1):
        est.observe_arrival((3 + 8 * k) * u)
        assert est.bdp_rounds() == want
    assert est.gap_ema == 7.125 * u
    assert not est.supports_growth(4)               # service-matched: veto
    assert est.supports_growth(2)


def test_adaptive_growth_vetoed_for_service_matched_producer():
    """One blocking producer over a slow wire: the pre-PR6 signal grew
    to the ceiling here (each leader found the pipeline 'full' of its
    predecessor); the BDP signal must keep depth at 1 once calibrated."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2, pipeline_depth=4,
                           adaptive_depth=True)
    rs.transports[0].inject(delay_s=0.01)
    for i in range(24):
        rs.log.append(b"m" * 32)       # blocking: G tracks L
    assert rs.log.pipeline_depth <= 2, rs.log.depth_trajectory
    assert rs.log.stats()["depth_bdp"] in (1, 2)
    rs.group.drain()
    rs.shutdown()


# --------------------------------------------------------------------- #
# DurableKV / ReplicaSet integration
# --------------------------------------------------------------------- #
def test_kvstore_ingest_front_end_round_trip():
    from repro.apps.kvstore import DurableKV
    dev, log = _local_log(pipeline_depth=2)
    kv = DurableKV(log, SyncPolicy(), ingest=IngestConfig())
    kv.put(b"k1", b"v1")
    pend = deque(kv.put_async(f"k{i}".encode(), b"w" * 16)
                 for i in range(2, 34))
    kv.flush()
    assert all(t.done and t.error is None for t in pend)
    assert kv.get(b"k1") == b"v1" and kv.get(b"k5") == b"w" * 16
    kv.close()
    relog = Log.open(dev, LogConfig(capacity=CAP))
    kv2 = DurableKV.recover(relog)
    assert kv2.get(b"k1") == b"v1" and len(kv2) == 33


def test_put_async_requires_ingest():
    from repro.apps.kvstore import DurableKV
    _, log = _local_log()
    kv = DurableKV(log, SyncPolicy())
    with pytest.raises(ValueError):
        kv.put_async(b"k", b"v")


def test_replica_set_attaches_and_shuts_down_ingest():
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2, pipeline_depth=2,
                           ingest=IngestConfig())
    assert rs.ingest is not None
    assert rs.attach_ingest() is rs.ingest          # built exactly once
    ts = [rs.ingest.append(b"r" * 16) for _ in range(16)]
    rs.ingest.drain()
    assert all(t.error is None for t in ts)
    assert rs.log.durable_lsn == 16
    rs.shutdown()                                   # closes engine first
    assert rs.ingest is None


# --------------------------------------------------------------------- #
# single-producer direct fast path (DESIGN.md §10)
# --------------------------------------------------------------------- #
def test_single_producer_takes_direct_path():
    """One producer on a local sync-ack log never pays the collector
    hop: every record goes scalar + blocking force on its own thread,
    zero waves, and recovery still sees the exact gapless multiset."""
    dev, log = _local_log(pipeline_depth=4)
    eng = IngestEngine(log, IngestConfig())
    tickets = [eng.append(f"d{i:04d}".encode().ljust(24, b"."))
               for i in range(64)]
    for t in tickets:
        assert t.wait(5.0) > 0 and t.error is None
        assert t.done                 # resolved before append returned
    st = eng.stats()
    assert st["direct"] == st["acked"] == 64
    assert st["waves"] == 0
    eng.close()
    relog = Log.open(dev, LogConfig(capacity=CAP))
    recovered = {lsn: bytes(p) for lsn, p in relog.iter_records()}
    assert sorted(recovered) == list(range(1, 65))
    for i, t in enumerate(tickets):
        assert recovered[t.lsn] == f"d{i:04d}".encode().ljust(24, b".")


def test_direct_path_latches_off_on_second_producer_and_rearms():
    _, log = _local_log(pipeline_depth=4)
    eng = IngestEngine(log, IngestConfig())
    for i in range(8):                        # phase 1: alone -> direct
        eng.append(b"solo" + bytes([i])).wait(5.0)
    assert eng.stats()["direct"] == 8

    other_done = threading.Event()

    def other():
        for i in range(8):
            eng.append(b"othr" + bytes([i])).wait(5.0)
        other_done.set()

    th = threading.Thread(target=other)
    th.start()
    th.join()
    assert other_done.is_set()
    for i in range(8):                        # phase 2: latched off
        eng.append(b"post" + bytes([i])).wait(5.0)
    st = eng.stats()
    assert st["acked"] == 24
    # the second thread's appends and everything after went through
    # the collector, not the fast path
    assert st["direct"] == 8
    assert st["waves"] > 0

    eng.drain()                               # idle again: latch re-arms
    eng.append(b"rearmed").wait(5.0)
    assert eng.stats()["direct"] == 9
    eng.close()


def test_direct_path_never_engages_when_it_cannot_help():
    # replicated log: the wave path owns quorum pipelining
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2, pipeline_depth=2,
                           ingest=IngestConfig())
    for _ in range(8):
        rs.ingest.append(b"r" * 16).wait(5.0)
    assert rs.ingest.stats()["direct"] == 0
    rs.shutdown()
    # freq policy: the deliberately-unforced tail stays with the collector
    _, log = _local_log(pipeline_depth=2)
    eng = IngestEngine(log, IngestConfig(), policy=FreqPolicy(4))
    for _ in range(8):
        eng.append(b"f" * 16)
    eng.drain()
    assert eng.stats()["direct"] == 0
    eng.close()
    # and the config switch turns it off outright
    _, log2 = _local_log(pipeline_depth=2)
    eng2 = IngestEngine(log2, IngestConfig(direct_path=False))
    eng2.append(b"x" * 16).wait(5.0)
    assert eng2.stats()["direct"] == 0
    eng2.close()


# --------------------------------------------------------------------- #
# fair shed admission (FIFO turn queue)
# --------------------------------------------------------------------- #
def test_shed_admission_is_fifo_not_wakeup_race():
    """Two producers wait for one freed slot: the slot goes to the
    longest-waiting producer (FIFO head), deterministically — the
    second sheds at its deadline."""
    _, log = _local_log(pipeline_depth=2)
    eng = IngestEngine(log, IngestConfig(
        queue_records=1, admission="shed", shed_deadline_s=1.5,
        flush_interval_s=60.0, flush_records=1 << 20,
        direct_path=False))
    # park the collector: nothing is ever flush-due, so the queue stays
    # exactly as admission control leaves it
    eng._flush_due_locked = lambda first_t: False
    eng.append(b"seed" * 4)               # queue now full (1/1)

    results = {}

    def producer(name):
        try:
            results[name] = eng.append(name.encode() * 4)
        except IngestShedError:
            results[name] = "shed"

    a = threading.Thread(target=producer, args=("aaaa",))
    a.start()
    time.sleep(0.15)                      # A is waiting at the head
    b = threading.Thread(target=producer, args=("bbbb",))
    b.start()
    time.sleep(0.15)                      # B queued behind A

    with eng._lock:                       # free exactly one slot
        t0 = eng._queue.popleft()
        eng._q_records -= 1
        eng._q_bytes -= t0.size
        eng._space.notify_all()
    a.join(timeout=5.0)
    b.join(timeout=5.0)

    assert not isinstance(results["aaaa"], str)   # head got the slot...
    assert results["bbbb"] == "shed"              # ...the tail shed
    assert eng.shed == 1
    del eng._flush_due_locked             # un-park for a clean close
    eng.close()


def test_shed_fairness_hot_producer_cannot_starve_slow_one():
    """Regression for the wakeup-race starvation: a 10:1 hot producer
    hammering a tiny queue must not shed out the slow producer — FIFO
    turns hand freed slots to whoever waited longest."""
    rs = build_replica_set(mode="local+remote", capacity=CAP, n_backups=1,
                           write_quorum=2, pipeline_depth=2,
                           ingest=IngestConfig(
                               queue_records=2, admission="shed",
                               shed_deadline_s=0.5))
    rs.transports[0].inject(delay_s=0.002)        # slow the drain
    eng = rs.ingest
    slow_tickets, hot_shed = [], [0]

    def hot():
        for i in range(100):
            try:
                eng.append(f"hot{i:04d}".encode().ljust(24, b"."))
            except IngestShedError:
                hot_shed[0] += 1

    def slow():
        for i in range(10):
            slow_tickets.append(
                eng.append(f"slw{i:04d}".encode().ljust(24, b".")))
            time.sleep(0.005)

    th_h = threading.Thread(target=hot)
    th_s = threading.Thread(target=slow)
    th_h.start()
    th_s.start()
    th_h.join()
    th_s.join()
    eng.drain()
    # the slow producer never shed and every one of its records acked
    assert len(slow_tickets) == 10
    for t in slow_tickets:
        assert t.wait(5.0) > 0 and t.error is None
    st = eng.stats()
    assert st["acked"] == st["submitted"] == 110 - hot_shed[0]
    assert st["failed"] == 0
    rs.shutdown()
