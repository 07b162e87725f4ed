"""The program's trace spans and counters (``repro.obs``).

One profiler session on the CPU records three phases, each inside a
span of the test's own: a replicated ``IngestEngine`` appending records
at the hash threshold, a ``Log.open`` of the primary's crash image, and
two ``Trainer`` steps with one async checkpoint.  The tests read the
session's ``.xplane.pb`` back: every listed name is there, spans nest
as the layers do, a round's issue, lane and retire spans share its
``round``, and the counters agree with the spans and with hand counts.
"""

import glob
import os
import signal
import time
from collections import defaultdict

import numpy as np
import pytest

import jax

from repro import obs
from repro.core import Log, LogConfig, PMEMDevice, build_replica_set
from repro.core.ingest import IngestConfig

MIB = 1 << 20
N_RECORDS = 6
SIZES = [MIB + 8 * k for k in range(N_RECORDS)]   # all at the hash threshold
WAVE_DELAY_S = 0.2
TIME_LIMIT_S = 240


class _TimeLimit:
    """SIGALRM-based limit on the test process's main thread."""

    def __init__(self, seconds: int):
        self.seconds = seconds

    def __enter__(self):
        def expire(signum, frame):
            raise TimeoutError(f"over the {self.seconds} s time limit")
        self.old = signal.signal(signal.SIGALRM, expire)
        signal.alarm(self.seconds)

    def __exit__(self, *exc):
        signal.alarm(0)
        signal.signal(signal.SIGALRM, self.old)


@pytest.fixture
def time_limit():
    with _TimeLimit(TIME_LIMIT_S):
        yield


class Span:
    __slots__ = ("name", "start", "end", "line", "meta")

    def __init__(self, name, start, end, line, meta):
        self.name, self.start, self.end = name, start, end
        self.line, self.meta = line, meta

    def inside(self, other) -> bool:
        return (self.line == other.line and other.start <= self.start
                and self.end <= other.end and self is not other)


def _read_spans(path):
    """Every ``arcadia.*`` and ``test.*`` host event, with its thread."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(("arcadia.", "test.")):
                    out.append(Span(ev.name, ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    (plane.name, li),
                                    {k: v for k, v in ev.stats}))
    return out


def _ingest_phase(state):
    rs = build_replica_set(mode="local+remote", capacity=16 * MIB,
                           n_backups=2, write_quorum=2,
                           ingest=IngestConfig(queue_records=1,
                                               queue_bytes=64 * MIB,
                                               flush_bytes=64 * MIB))
    log, eng = rs.log, rs.ingest
    # the first wave is held in complete_batch, so the producer's second
    # append finds the queue full and its record waits in the queue
    complete_batch, held = log.complete_batch, []

    def slow_complete(batch):
        if not held:
            held.append(batch)
            time.sleep(WAVE_DELAY_S)
        return complete_batch(batch)

    log.complete_batch = slow_complete
    rng = np.random.default_rng(0)
    payloads = [rng.bytes(n) for n in SIZES]
    s0, l0 = eng.stats(), log.stats()
    tickets = [eng.append(p) for p in payloads]
    lsns = [t.wait(timeout=60) for t in tickets]
    eng.drain(timeout=60)
    rs.group.drain(timeout=60)
    state.update(ingest=(s0, eng.stats()), log=(l0, log.stats()),
                 lsns=lsns, rs=rs)


def _open_phase(state):
    rs = state["rs"]
    log = Log.open(rs.primary_dev.crash(), rs.cfg)
    state["opened_lsn"] = log.durable_lsn


def _train_phase(state):
    from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                                  ObjectStore, ReplicatedStore)
    from repro.configs import reduced_config
    from repro.data import DataConfig, SyntheticDataset
    from repro.optim import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = reduced_config("qwen2-7b")
    data = SyntheticDataset(cfg, DataConfig(batch=2, seq_len=32))
    store = ReplicatedStore([ObjectStore(f"s{i}") for i in range(2)],
                            write_quorum=1)
    log = Log.create(PMEMDevice((1 << 18) + 4096),
                     LogConfig(capacity=1 << 18))
    mgr = CheckpointManager(store, log, CheckpointConfig(force_freq=1))
    opt = OptConfig(name="adamw", lr=3e-3, warmup_steps=2,
                    decay_steps=1000, clip_norm=1.0)
    tr = Trainer(cfg, opt, data, mgr,
                 TrainerConfig(total_steps=2, ckpt_every=2,
                               async_ckpt=True))
    tr.init_or_restore()
    rep = tr.run()
    mgr.close()
    state["train"] = rep


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Runs the three phases under one profiler session."""
    state = {}
    tmp = str(tmp_path_factory.mktemp("obs_trace"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with _TimeLimit(TIME_LIMIT_S):
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            for name, phase in (("test.ingest", _ingest_phase),
                                ("test.open", _open_phase),
                                ("test.train", _train_phase)):
                with jax.profiler.TraceAnnotation(name):
                    phase(state)
        finally:
            jax.profiler.stop_trace()
        state["rs"].shutdown()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = _read_spans(path)
    phases = {s.name: s for s in spans if s.name.startswith("test.")}
    return state, [s for s in spans if s.name.startswith("arcadia.")], \
        phases


def _during(spans, phase):
    return [s for s in spans if phase.start <= s.start <= phase.end]


def test_every_listed_name_is_emitted_and_no_other(traced):
    _, spans, _ = traced
    names = {s.name for s in spans}
    assert names <= obs.NAMES, names - obs.NAMES
    assert names == obs.NAMES, obs.NAMES - names
    assert len(obs.NAMES) == 25


@pytest.mark.parametrize("child,parent", [
    (obs.LOG_RESERVE, obs.INGEST_WAVE),
    (obs.LOG_COPY, obs.INGEST_WAVE),
    (obs.LOG_COMPLETE, obs.INGEST_WAVE),
    (obs.LOG_HASH, obs.LOG_COMPLETE),
    (obs.CHECKSUM_CALL, obs.LOG_HASH),
    (obs.LOG_ISSUE, obs.INGEST_WAVE),
    (obs.REPL_POST, obs.LOG_ISSUE),
    (obs.LOG_FLUSH, obs.LOG_ISSUE),
    (obs.OPEN_SNAPSHOT, obs.OPEN),
    (obs.OPEN_PLAN, obs.OPEN),
    (obs.OPEN_VALIDATE, obs.OPEN),
    (obs.OPEN_LANES, obs.OPEN_VALIDATE),
    (obs.CHECKSUM_CALL, obs.OPEN_VALIDATE),
    (obs.LOG_RESERVE, obs.TRAIN_JOURNAL),
    (obs.LOG_COMPLETE, obs.CKPT_WRITE),
    (obs.CKPT_FETCH, obs.CKPT_WRITE),
])
def test_spans_nest_as_the_layers_do(traced, child, parent):
    _, spans, _ = traced
    parents = [s for s in spans if s.name == parent]
    kids = [s for s in spans if s.name == child]
    assert parents and kids
    assert any(k.inside(p) for k in kids for p in parents)


def test_every_hash_call_of_the_append_path_is_inside_complete(traced):
    _, spans, phases = traced
    ing = _during(spans, phases["test.ingest"])
    hashes = [s for s in ing if s.name == obs.LOG_HASH]
    completes = [s for s in ing if s.name == obs.LOG_COMPLETE]
    assert len(hashes) == N_RECORDS
    assert all(any(h.inside(c) for c in completes) for h in hashes)


def test_open_holds_one_batched_hash_call(traced):
    state, spans, phases = traced
    op = _during(spans, phases["test.open"])
    assert [s.name for s in op if s.name == obs.OPEN] == [obs.OPEN]
    calls = [s for s in op if s.name == obs.CHECKSUM_CALL]
    assert len(calls) == 1
    assert state["opened_lsn"] == max(state["lsns"])


def test_round_joins_issue_lane_and_retire(traced):
    _, spans, phases = traced
    ing = _during(spans, phases["test.ingest"])
    issued = {s.meta["round"] for s in ing
              if s.name == obs.LOG_ISSUE and "round" in s.meta}
    retired = [s.meta["round"] for s in ing if s.name == obs.LOG_RETIRE]
    lanes = [s.meta.get("round") for s in ing if s.name == obs.REPL_LANE]
    assert retired and set(retired) == issued
    assert len(retired) == len(set(retired))
    # one wave, one record, one round each; two backups: each round's
    # range goes down two lanes
    assert len(retired) == N_RECORDS
    assert sorted(lanes) == sorted(retired * 2)


def test_admission_wait_is_a_span(traced):
    _, spans, phases = traced
    ing = _during(spans, phases["test.ingest"])
    admits = [s for s in ing if s.name == obs.INGEST_ADMIT]
    assert admits
    assert max(s.end - s.start for s in admits) >= 0.5 * WAVE_DELAY_S * 1e9


def test_ingest_counters_by_hand(traced):
    state, spans, phases = traced
    s0, s1 = state["ingest"]
    # every record went through the collector (no direct path with
    # backups); the second one waited out the held first wave
    assert s1["collected"] - s0["collected"] == N_RECORDS
    assert s1["acked"] - s0["acked"] == N_RECORDS
    wait = s1["queue_wait_s"] - s0["queue_wait_s"]
    assert 0.5 * WAVE_DELAY_S <= wait
    ph = phases["test.ingest"]
    assert wait <= N_RECORDS * (ph.end - ph.start) * 1e-9


def test_round_counters_agree_with_the_spans(traced):
    state, spans, phases = traced
    l0, l1 = state["log"]
    ing = _during(spans, phases["test.ingest"])
    retires = {s.meta["round"]: s for s in ing if s.name == obs.LOG_RETIRE}
    issues = {s.meta["round"]: s for s in ing
              if s.name == obs.LOG_ISSUE and "round" in s.meta}
    n = l1["rounds_retired"] - l0["rounds_retired"]
    assert n == len(retires) == N_RECORDS
    wall = l1["round_wall_s"] - l0["round_wall_s"]
    # each round's wall time lies between its spans' inner and outer gaps
    lo = sum(max(0, retires[r].start - issues[r].end) for r in retires)
    hi = sum(retires[r].end - issues[r].start for r in retires)
    assert lo * 1e-9 <= wall <= hi * 1e-9


def test_trainer_spans_per_step(traced):
    state, spans, phases = traced
    tr = _during(spans, phases["test.train"])
    count = defaultdict(int)
    for s in tr:
        count[s.name] += 1
    assert state["train"].steps_run == 2
    for name in (obs.TRAIN_BATCH, obs.TRAIN_STEP, obs.TRAIN_LOSS,
                 obs.TRAIN_JOURNAL):
        assert count[name] == 2, name
    assert count[obs.CKPT_SNAPSHOT] == 1 and count[obs.CKPT_WRITE] == 1
    assert count[obs.CKPT_FETCH] == 1
    # the save's host copy and write run on the save worker, off the
    # step loop
    snap = next(s for s in tr if s.name == obs.CKPT_SNAPSHOT)
    write = next(s for s in tr if s.name == obs.CKPT_WRITE)
    fetch = next(s for s in tr if s.name == obs.CKPT_FETCH)
    assert write.line != snap.line and write.start >= snap.end
    assert fetch.inside(write)


def test_counters_of_a_local_log_by_hand(time_limit):
    """Three forced appends on a log with no backups: three rounds, each
    retired inline by its own force."""
    log = Log.create(PMEMDevice((1 << 16) + 4096),
                     LogConfig(capacity=1 << 16))
    t0 = time.monotonic()
    for k in range(3):
        log.append(b"x" * (8 + k), freq=1)
    el = time.monotonic() - t0
    st = log.stats()
    assert st["rounds_retired"] == 3
    assert 0 < st["round_wall_s"] <= el


def test_counters_of_an_idle_engine_stay_at_zero(time_limit):
    rs = build_replica_set(mode="local+remote", capacity=1 << 20,
                           n_backups=1, write_quorum=2,
                           ingest=IngestConfig())
    try:
        st = rs.ingest.stats()
        assert st["collected"] == 0 and st["queue_wait_s"] == 0.0
        assert rs.log.stats()["rounds_retired"] == 0
    finally:
        rs.shutdown()


def test_span_costs_little_with_no_session(time_limit):
    """No session running: a span is a no-op context manager."""
    t0 = time.perf_counter()
    for _ in range(10000):
        with obs.span(obs.LOG_HASH):
            pass
    assert (time.perf_counter() - t0) / 10000 < 50e-6


def test_counter_names_are_the_listed_ones(traced):
    """Every counter the program keeps is in ``obs.COUNTERS``, under the
    name a reader takes it by."""
    state, _, _ = traced
    s0, _ = state["ingest"]
    l0, _ = state["log"]
    assert obs.COUNTERS == {obs.COLLECTED, obs.QUEUE_WAIT_S,
                            obs.ROUNDS_RETIRED, obs.ROUND_WALL_S,
                            obs.CKPTS_SAVED, obs.CKPTS_SKIPPED,
                            obs.MOE_ROWS, obs.MOE_MAX_ROWS}
    assert {obs.COLLECTED, obs.QUEUE_WAIT_S} <= set(s0)
    assert {obs.ROUNDS_RETIRED, obs.ROUND_WALL_S} <= set(l0)
    rep = vars(state["train"])
    assert {obs.CKPTS_SAVED, obs.CKPTS_SKIPPED, obs.MOE_ROWS,
            obs.MOE_MAX_ROWS} <= set(rep)
    assert rep[obs.CKPTS_SAVED] == 1 and rep[obs.CKPTS_SKIPPED] == 0
    # a model without held experts counts no rows
    assert rep[obs.MOE_ROWS] == rep[obs.MOE_MAX_ROWS] == 0


def test_moe_counters_by_hand(time_limit):
    """One step of a model with held experts: the trainer's row counters
    are the step's own, read with its loss."""
    from repro.checkpoint import (CheckpointManager, ObjectStore,
                                  ReplicatedStore)
    from repro.configs import reduced_config
    from repro.data import DataConfig, SyntheticDataset
    from repro.models import model as M
    from repro.optim import OptConfig
    from repro.train.trainer import Trainer, TrainerConfig
    cfg = reduced_config("nemotron3-nano-30b-a3b")
    data = SyntheticDataset(cfg, DataConfig(batch=2, seq_len=32))
    store = ReplicatedStore([ObjectStore("s0")], write_quorum=1)
    log = Log.create(PMEMDevice((1 << 18) + 4096),
                     LogConfig(capacity=1 << 18))
    mgr = CheckpointManager(store, log)
    tr = Trainer(cfg, OptConfig(), data, mgr,
                 TrainerConfig(total_steps=1, ckpt_every=10))
    tr.init_or_restore()
    batch = {k: jax.numpy.asarray(v) for k, v in data.batch_at(0).items()}
    _, want = jax.jit(lambda p: M.forward_train(p, cfg, batch))(
        tr.state["params"])
    rep = tr.run()
    mgr.close()
    assert rep.moe_rows == int(want[obs.MOE_ROWS]) > 0
    assert rep.moe_max_rows == int(want[obs.MOE_MAX_ROWS])
    # 3 expert layers, 64 tokens, 3 of 16 experts a token, 4 held: at
    # most min(3, 4) held experts a token
    assert rep.moe_rows <= 3 * 64 * 3
    assert rep.moe_max_rows * cfg.experts_held >= rep.moe_rows
