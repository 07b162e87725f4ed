"""The program's own spans (``arcadia.*``, listed in ``src/repro/obs.py``)
read from a profiler trace, and the readings built on them and on the
program's counters.

A program span lands on the host plane of the thread that opened it,
on the clock of the device's ops.  On one thread spans nest, so each
has a self time: its window-clipped length less that of the program
spans directly inside it.  ``idle_by_program_span`` splits the device's
idle time in the window by the innermost program span open on each
thread, as ``Trace.idle_gaps`` splits it by the benchmark's spans.

A trace of a program without these spans reads nothing: every reading
is then None.
"""

from __future__ import annotations

from collections import defaultdict
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .trace import Trace, _merge

PREFIX = "arcadia."
NONE = "none"

# (start ns, end ns, name, thread) — thread is (plane name, line index)
Span = Tuple[float, float, str, Tuple[str, int]]


def read_program_spans(path: str) -> List[Span]:
    """Every ``arcadia.*`` event on the host planes of a trace file."""
    from jax.profiler import ProfileData
    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    out.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                ev.name, (plane.name, li)))
    return out


class ProgramSpans:
    """Program spans against one window ``[t0, t1]`` (ns)."""

    def __init__(self, spans: List[Span], t0: float, t1: float):
        self.t0, self.t1 = t0, t1
        # start ascending, the longer first at a tie: a parent precedes
        # the children it holds
        self.spans = sorted(spans, key=lambda s: (s[3], s[0], -s[1]))
        self.parent: List[Optional[int]] = [None] * len(self.spans)
        stack: List[int] = []
        thread = None
        for i, (a, b, _, th) in enumerate(self.spans):
            if th != thread:
                stack, thread = [], th
            while stack and self.spans[stack[-1]][1] < b:
                stack.pop()
            if stack and self.spans[stack[-1]][0] <= a:
                self.parent[i] = stack[-1]
            stack.append(i)

    def _clip(self, a: float, b: float) -> float:
        return max(0.0, min(b, self.t1) - max(a, self.t0))

    def totals(self) -> Dict[str, List[float]]:
        """name -> [clipped seconds, clipped self seconds, count of the
        spans that start in the window]."""
        own = [self._clip(a, b) for a, b, _, _ in self.spans]
        inner = [0.0] * len(self.spans)
        for i, p in enumerate(self.parent):
            if p is not None:
                inner[p] += own[i]
        out: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0.0, 0])
        for i, (a, _, name, _) in enumerate(self.spans):
            row = out[name]
            row[0] += own[i] * 1e-9
            row[1] += (own[i] - inner[i]) * 1e-9
            row[2] += int(self.t0 <= a <= self.t1)
        return dict(out)

    def seconds(self, name: str, inside: Optional[str] = None) -> float:
        """Clipped seconds of ``name`` spans; with ``inside``, only of
        those that lie within a span of that name on their thread."""
        tot = 0.0
        for i, (a, b, n, _) in enumerate(self.spans):
            if n != name:
                continue
            if inside is not None and not self._within(i, inside):
                continue
            tot += self._clip(a, b)
        return tot * 1e-9

    def _within(self, i: int, name: str) -> bool:
        p = self.parent[i]
        while p is not None:
            if self.spans[p][2] == name:
                return True
            p = self.parent[p]
        return False

    def durations_ending_in_window(self, name: str) -> List[float]:
        """Whole seconds of the ``name`` spans that end in the window."""
        return [(b - a) * 1e-9 for a, b, n, _ in self.spans
                if n == name and self.t0 <= b <= self.t1]


def _busy_before(trace: Trace) -> Callable[[float], float]:
    """Device busy ns of the first chip from the window's start to t."""
    if not trace.devices:
        return lambda t: 0.0
    d = trace.devices[sorted(trace.devices)[0]]
    s, e = trace._clip(d)
    busy = [(a, b) for a, b in _merge(s, e) if b > a]
    starts = np.asarray([a for a, _ in busy] or [trace.t0])
    ends = np.asarray([b for _, b in busy] or [trace.t0])
    cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

    def before(t: float) -> float:
        i = int(np.searchsorted(starts, t, side="right"))
        if i == 0:
            return 0.0
        return float(cum[i - 1] + min(t, ends[i - 1]) - starts[i - 1])
    return before


def idle_by_program_span(trace: Trace, spans: List[Span],
                         k: int = 10) -> List[List]:
    """Device idle seconds in the window split by what the program was
    doing: the window is cut at every program-span boundary, each
    stretch is labelled with the sorted, ``+``-joined set of the
    innermost program span open on each thread (``none`` if no thread
    has one open), and charged its length less the device's busy time
    in it.  The ``k`` largest, largest first."""
    t0, t1 = trace.t0, trace.t1
    busy_before = _busy_before(trace)
    # (time, close before open, an outer span opens before its inner
    # ones, span)
    events = [(t0, 0, 0.0, -1), (t1, 0, 0.0, -1)]
    for idx, (a, b, _, _) in enumerate(spans):
        if b > t0 and a < t1:
            events.append((max(a, t0), 1, -b, idx))
            events.append((min(b, t1), -1, 0.0, idx))
    events.sort()
    open_: Dict[Tuple[str, int], List[int]] = defaultdict(list)
    acc: Dict[str, float] = defaultdict(float)
    prev = t0
    for t, delta, _, idx in events:
        if t > prev:
            inner = {spans[st[-1]][2] for st in open_.values() if st}
            label = "+".join(sorted(inner)) or NONE
            acc[label] += ((t - prev)
                           - (busy_before(t) - busy_before(prev))) * 1e-9
            prev = t
        if delta > 0:
            open_[spans[idx][3]].append(idx)
        elif delta < 0:
            st = open_[spans[idx][3]]
            if idx in st:
                st.remove(idx)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
    return [[n, v] for n, v in top if v > 0]


# -- readings ------------------------------------------------------------ #
def _ratio(num: Optional[float], den, scale: float = 1.0
           ) -> Optional[float]:
    if num is None or not den:
        return None
    return scale * num / den


def counter_delta(before: Dict, after: Dict) -> Dict[str, float]:
    """Window change of the program counters the readings use; a
    counter the program does not have is left out."""
    keys = ("collected", "queue_wait_s", "rounds_retired", "round_wall_s")
    return {k: after[k] - before[k] for k in keys
            if k in before and k in after}


def readings(ps: ProgramSpans, counters: Dict[str, float]
             ) -> Dict[str, float]:
    """The per-layer readings of the program's spans and counters in
    one window; a reading with nothing to read is left out."""
    tot = ps.totals()

    def total(name: str) -> float:
        return tot.get(name, [0.0, 0.0, 0])[0]

    def self_s(name: str) -> float:
        return tot.get(name, [0.0, 0.0, 0])[1]

    def count(name: str) -> int:
        return tot.get(name, [0.0, 0.0, 0])[2]

    hashes = count("arcadia.log.hash")
    opens = count("arcadia.open")
    steps = count("arcadia.train.step")
    writes = ps.durations_ending_in_window("arcadia.ckpt.write")
    out = {
        "queue_wait_ms": _ratio(counters.get("queue_wait_s"),
                                counters.get("collected"), 1e3),
        "round_ms": _ratio(counters.get("round_wall_s"),
                           counters.get("rounds_retired"), 1e3),
        # self time: the hash, a span inside complete, is not in it
        "append_host_ms": _ratio(
            self_s("arcadia.log.reserve") + self_s("arcadia.log.copy")
            + self_s("arcadia.log.complete"), hashes, 1e3),
        "hash_call_ms.append": _ratio(total("arcadia.log.hash"), hashes,
                                      1e3),
        "open_plan_s": _ratio(total("arcadia.open.snapshot")
                              + total("arcadia.open.plan"), opens),
        "open_lanes_s": _ratio(total("arcadia.open.lanes"), opens),
        "open_hash_s": _ratio(ps.seconds("arcadia.checksum.call",
                                         inside="arcadia.open"), opens),
        "step_gap_ms": _ratio(total("arcadia.train.batch")
                              + total("arcadia.train.journal"), steps, 1e3),
        "ckpt_snapshot_ms": _ratio(total("arcadia.ckpt.snapshot"),
                                   count("arcadia.ckpt.snapshot"), 1e3),
        "ckpt_write_s": _ratio(sum(writes), len(writes)),
    }
    return {k: v for k, v in out.items() if v is not None}
