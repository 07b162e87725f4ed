"""The reduction of the program's own spans (``bench/lib/spans.py``).

By hand on made-up spans; on ``data/spans.xplane.pb``, a small trace
recorded on a TPU v5e by ``bench/tools/record_spans.py`` (four appends
through one producer of the ingest engine and one ``Log.open``, inside
``bench.window``); on the older ``data/probe.xplane.pb``, whose program
had no such spans, where every existing reading keeps its value and the
new ones read nothing; and through ``bench/tools/span_report.py`` on a
tiny WAL cell on the CPU.
"""

import os

import numpy as np
import pytest

from bench_tiny import ROOT, SEED, wal_cell
from bench.lib import common, readers, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data")
SPANS = os.path.join(DATA, "spans.xplane.pb")
PROBE = os.path.join(DATA, "probe.xplane.pb")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}
A, B = ("/host:CPU", 0), ("/host:CPU", 1)


def _trace(t0, t1, busy=()):
    names = [f"%op{i} = op" for i in range(len(busy))]
    ops = trace.DeviceOps(names, np.array([a for a, _ in busy], float),
                          np.array([b for _, b in busy], float), [])
    return trace.Trace({"/device:TPU:0": ops} if busy else {},
                       [(t0, t1, trace.WINDOW_SPAN)])


# -- by hand ------------------------------------------------------------- #
def test_totals_self_and_counts_by_hand():
    """Window [10, 110] ns.  Thread A: complete [0, 100] holding hash
    [20, 50], which holds a checksum call [30, 40]; thread B: a lane
    write [60, 120]."""
    ps = spans.ProgramSpans(
        [(0, 100, "arcadia.log.complete", A), (20, 50, "arcadia.log.hash", A),
         (30, 40, "arcadia.checksum.call", A),
         (60, 120, "arcadia.repl.lane", B)], 10, 110)
    want = {"arcadia.log.complete": [90, 60, 0],   # starts before the window
            "arcadia.log.hash": [30, 20, 1],
            "arcadia.checksum.call": [10, 10, 1],
            "arcadia.repl.lane": [50, 50, 1]}
    tot = ps.totals()
    assert set(tot) == set(want)
    for name, (secs, own, n) in tot.items():
        assert [secs * 1e9, own * 1e9, n] == pytest.approx(want[name])
    assert ps.seconds("arcadia.checksum.call",
                      inside="arcadia.log.complete") == pytest.approx(10e-9)
    assert ps.seconds("arcadia.repl.lane",
                      inside="arcadia.log.complete") == 0.0
    assert ps.durations_ending_in_window("arcadia.log.hash") == \
        pytest.approx([30e-9])
    assert ps.durations_ending_in_window("arcadia.repl.lane") == []


def test_idle_by_innermost_span_on_each_thread_by_hand():
    """Window [0, 100], busy [10, 20] and [50, 60].  Thread A: X [0, 40]
    holding Z [5, 15]; thread B: Y [30, 70]."""
    t = _trace(0.0, 100.0, busy=[(10.0, 20.0), (50.0, 60.0)])
    got = {n: v * 1e9 for n, v in spans.idle_by_program_span(
        t, [(0, 40, "arcadia.X", A), (5, 15, "arcadia.Z", A),
            (30, 70, "arcadia.Y", B)], k=10)}
    assert got == pytest.approx({
        "arcadia.X": 5 + 10, "arcadia.Z": 5, "arcadia.X+arcadia.Y": 10,
        "arcadia.Y": 20, "none": 30})
    assert sum(got.values()) == pytest.approx(100 - 20)


def test_one_name_open_on_two_threads_is_one_label():
    t = _trace(0.0, 10.0)
    got = spans.idle_by_program_span(
        t, [(0, 10, "arcadia.log.hash", A), (0, 10, "arcadia.log.hash", B)])
    assert got == [["arcadia.log.hash", pytest.approx(10e-9)]]


def test_readings_by_hand():
    """Two appends (reserve 1, copy 2, complete 10 holding a 6 ns hash,
    each), one open, two trainer steps and one save, in [0, 1000] ns."""
    ss = []
    for base in (0, 100):
        ss += [(base, base + 1, "arcadia.log.reserve", A),
               (base + 1, base + 3, "arcadia.log.copy", A),
               (base + 3, base + 13, "arcadia.log.complete", A),
               (base + 4, base + 10, "arcadia.log.hash", A)]
    ss += [(200, 300, "arcadia.open", B),
           (200, 220, "arcadia.open.snapshot", B),
           (220, 230, "arcadia.open.plan", B),
           (230, 290, "arcadia.open.validate", B),
           (230, 250, "arcadia.open.lanes", B),
           (250, 290, "arcadia.checksum.call", B),
           (400, 404, "arcadia.train.batch", A),
           (404, 410, "arcadia.train.step", A),
           (410, 430, "arcadia.train.loss", A),
           (430, 432, "arcadia.train.journal", A),
           (500, 504, "arcadia.train.batch", A),
           (504, 510, "arcadia.train.step", A),
           (510, 520, "arcadia.ckpt.snapshot", A),
           (520, 900, "arcadia.ckpt.write", B)]
    counters = {"collected": 4, "queue_wait_s": 0.010,
                "rounds_retired": 5, "round_wall_s": 0.020}
    got = spans.readings(spans.ProgramSpans(ss, 0, 1000), counters)
    assert got == pytest.approx({
        "queue_wait_ms": 2.5, "round_ms": 4.0,
        "append_host_ms": 1e-6 * (1 + 2 + 4) * 2 / 2,
        "hash_call_ms.append": 1e-6 * 6,
        "open_plan_s": 1e-9 * 30, "open_lanes_s": 1e-9 * 20,
        "open_hash_s": 1e-9 * 40,
        "step_gap_ms": 1e-6 * (4 + 2 + 4) / 2,
        "ckpt_snapshot_ms": 1e-6 * 10, "ckpt_write_s": 1e-9 * 380})
    # a save still running at the window's close is not counted
    late = spans.readings(spans.ProgramSpans(ss, 0, 800), {})
    assert "ckpt_write_s" not in late


def test_counter_delta_leaves_out_what_the_program_lacks():
    assert spans.counter_delta({"collected": 1, "acked": 1},
                               {"collected": 4, "acked": 9}) == \
        {"collected": 3}
    assert spans.readings(spans.ProgramSpans([], 0, 1), {}) == {}


# -- the recorded chip trace --------------------------------------------- #
@pytest.fixture(scope="module")
def chip():
    t = trace.Trace.from_file(SPANS)
    ps = spans.read_program_spans(SPANS)
    return t, ps, spans.ProgramSpans(ps, t.t0, t.t1)


def test_chip_trace_counts_by_hand(chip):
    t, raw, ps = chip
    counts = {k: v[2] for k, v in ps.totals().items()}
    # four records through the engine, one wave, round and hash each,
    # two backups; the open makes the fifth checksum call
    assert counts == {
        "arcadia.ingest.wave": 4, "arcadia.ingest.ack": 4,
        "arcadia.log.reserve": 4, "arcadia.log.copy": 4,
        "arcadia.log.complete": 4, "arcadia.log.hash": 4,
        "arcadia.checksum.call": 5, "arcadia.log.issue": 4,
        "arcadia.repl.post": 8, "arcadia.log.flush": 4,
        "arcadia.repl.lane": 8, "arcadia.log.retire": 4,
        "arcadia.open": 1, "arcadia.open.snapshot": 1,
        "arcadia.open.plan": 1, "arcadia.open.validate": 1,
        "arcadia.open.lanes": 1}
    assert {s[2] for s in raw} <= _listed_names()


def _listed_names():
    import sys
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro import obs
    return obs.NAMES


def test_chip_trace_readings_by_hand(chip):
    """Each reading from the span totals of the recorded trace: four
    hashed records (reserve 3.278181, copy 4.652691 and complete self
    1.120771 ms in all; hash 18.131038 ms) and one open (snapshot
    25.942831 ms, plan 0.04333 ms, lanes 7.009653 ms, its checksum call
    15.464507 ms)."""
    _, _, ps = chip
    got = spans.readings(ps, {})
    assert got == pytest.approx({
        "append_host_ms": (3.278181 + 4.652691 + 1.120771) / 4,
        "hash_call_ms.append": 18.131038 / 4,
        "open_plan_s": 0.025942831 + 0.00004333,
        "open_lanes_s": 0.007009653,
        "open_hash_s": 0.015464507}, rel=1e-6)


def test_chip_trace_rounds_join_across_threads():
    """Issue, lane and retire spans of each round carry its end LSN; the
    lane writes run on the two backups' threads, not the issuer's."""
    from jax.profiler import ProfileData
    by = {}
    for plane in ProfileData.from_file(SPANS).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                meta = dict(ev.stats)
                if "round" in meta:
                    by.setdefault(ev.name, []).append((meta["round"], li))
    rounds = sorted(r for r, _ in by["arcadia.log.issue"])
    assert rounds == [1, 2, 3, 4]
    assert sorted(r for r, _ in by["arcadia.log.retire"]) == rounds
    assert sorted(r for r, _ in by["arcadia.repl.lane"]) == \
        sorted(rounds * 2)
    issuers = {li for _, li in by["arcadia.log.issue"]}
    lanes = {li for _, li in by["arcadia.repl.lane"]}
    assert len(lanes) == 2 and not lanes & issuers


def test_chip_trace_idle_sums_to_the_windows_idle(chip):
    t, raw, _ = chip
    idle = spans.idle_by_program_span(t, raw, k=1000)
    assert sum(v for _, v in idle) == pytest.approx(
        t.window_s - t.busy_s(), rel=1e-9)
    labels = [n for n, _ in idle]
    assert len(labels) == len(set(labels))
    assert all(n == spans.NONE or n.startswith("arcadia.")
               for n in labels)


def test_chip_trace_open_matches_the_benchmark_span(chip):
    """The program's ``arcadia.open`` and the benchmark's
    ``bench.log_open`` around it are on one clock."""
    t, _, ps = chip
    (a, b), = [(a, b) for a, b, n in t.spans if n == "bench.log_open"]
    opened = ps.totals()["arcadia.open"][0]
    assert opened <= (b - a) * 1e-9
    assert opened == pytest.approx((b - a) * 1e-9, rel=0.02)
    got = spans.readings(ps, {})
    assert got["open_plan_s"] + got["open_lanes_s"] + got["open_hash_s"] \
        <= opened


# -- a trace with no program spans --------------------------------------- #
def test_probe_trace_keeps_every_existing_reading():
    t = trace.Trace.from_file(PROBE)
    assert t.busy_s() == pytest.approx(7.3692e-05)
    top = t.top_ops(3)
    assert [n for n, _ in top] == ["ssd.1", "fusion", "reshape.12"]
    assert [v for _, v in top] == pytest.approx(
        [2.8975e-05, 1.2586e-05, 8.428e-06])
    assert dict(t.idle_gaps()) == pytest.approx({
        "bench.append": 0.002730959, "bench.train_step": 0.001508009,
        "bench.window": 8.26e-06})
    need = 12 + (1 << 20)
    assert readers.hash_roofline(
        {"trace": t, "counters": {"hashed_bytes": need},
         "peaks": PEAKS}) == pytest.approx(100 * need / 819e9 / 6.05e-06)
    assert readers.ssd_roofline({"trace": t, "peaks": PEAKS}) == \
        (pytest.approx(25.821662184043543), "memory")
    assert readers.device_idle({"trace": t}) == pytest.approx(
        100 * (1 - 7.3692e-05 / 0.00432092))


def test_probe_trace_reads_no_program_spans():
    t = trace.Trace.from_file(PROBE)
    raw = spans.read_program_spans(PROBE)
    assert raw == []
    assert spans.readings(spans.ProgramSpans(raw, t.t0, t.t1), {}) == {}
    idle = spans.idle_by_program_span(t, raw)
    assert [n for n, _ in idle] == [spans.NONE]
    assert idle[0][1] == pytest.approx(t.window_s - t.busy_s())


# -- the tool on the CPU ------------------------------------------------- #
@pytest.mark.parametrize("name", ["wal-large.sync1", "wal-large.recover"])
def test_span_report_on_a_tiny_cell(name, tmp_path, monkeypatch):
    """``span_report.report`` on a tiny WAL cell on the CPU: the run is
    correct and every reading of the cell's layers is positive.  (The
    CPU has no peaks in the table, so the test gives it the v5e's.)"""
    import jax
    from bench import run
    from bench.tools import span_report
    monkeypatch.setattr(run, "_peaks", lambda devices: PEAKS)
    out = span_report.report(wal_cell(name, SEED), 0.5, jax.devices(),
                             common.benchmark_spec(ROOT), 0.0,
                             str(tmp_path / "trace"))
    assert out["correct"], out["checks"]
    p = out["program"]
    want = (("open_plan_s", "open_lanes_s", "open_hash_s")
            if name.endswith("recover") else
            ("queue_wait_ms", "round_ms", "append_host_ms",
             "hash_call_ms.append"))
    assert set(want) <= set(p["readings"])
    assert all(np.isfinite(v) and v > 0 for v in p["readings"].values())
    assert p["idle_by_program_span"]
    assert p["end_to_end_traced"]
