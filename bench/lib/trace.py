"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO instruction, named by the instruction's text
(``%name = shape op(operands), ...``).  Host planes hold the benchmark's
own ``TraceAnnotation`` spans, whose names start with ``bench.``; both
kinds of plane share one clock.  The window is the first ``bench.window``
span.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass
from typing import Callable, Dict, Iterator, List, Tuple

import numpy as np

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def _merge(starts: np.ndarray, ends: np.ndarray) -> List[Tuple[float, float]]:
    order = np.argsort(starts, kind="stable")
    out: List[Tuple[float, float]] = []
    for s, e in zip(starts[order], ends[order]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((float(s), float(e)))
    return out


@dataclass
class DeviceOps:
    names: List[str]
    start: np.ndarray      # ns
    end: np.ndarray        # ns
    modules: List[Tuple[float, float, str]] = None   # program runs


class Trace:
    """Device ops per chip, benchmark host spans, and the window."""

    def __init__(self, devices: Dict[str, DeviceOps],
                 spans: List[Tuple[float, float, str]]):
        self.devices = devices
        self.spans = sorted(spans)
        win = [s for s in self.spans if s[2] == WINDOW_SPAN]
        if not win:
            raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
        self.t0, self.t1 = win[0][0], win[0][1]

    # -- loading --------------------------------------------------------- #
    @classmethod
    def from_file(cls, path: str) -> "Trace":
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        devices: Dict[str, DeviceOps] = {}
        spans: List[Tuple[float, float, str]] = []
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                names, st, en, mods = [], [], [], []
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for ev in line.events:
                            names.append(ev.name)
                            st.append(ev.start_ns)
                            en.append(ev.start_ns + ev.duration_ns)
                    elif line.name == "XLA Modules":
                        mods.extend((ev.start_ns,
                                     ev.start_ns + ev.duration_ns, ev.name)
                                    for ev in line.events)
                if names:
                    devices[plane.name] = DeviceOps(
                        names, np.asarray(st, np.float64),
                        np.asarray(en, np.float64), sorted(mods))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append((ev.start_ns,
                                          ev.start_ns + ev.duration_ns,
                                          ev.name))
        return cls(devices, spans)

    # -- window ---------------------------------------------------------- #
    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _clip(self, d: DeviceOps) -> Tuple[np.ndarray, np.ndarray]:
        s = np.clip(d.start, self.t0, self.t1)
        e = np.clip(d.end, self.t0, self.t1)
        return s, e

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips
        that ran any (0.0 when no chip ran an operation)."""
        if not self.devices:
            return 0.0
        tot = 0.0
        for d in self.devices.values():
            s, e = self._clip(d)
            tot += sum(b - a for a, b in _merge(s, e))
        return tot * 1e-9 / len(self.devices)

    def ops(self, match: Callable[[str], bool]
            ) -> Iterator[Tuple[str, float]]:
        """(instruction text, window-clipped seconds) of every op on every
        chip that ``match`` accepts and that ran inside the window."""
        for d in self.devices.values():
            s, e = self._clip(d)
            for name, a, b in zip(d.names, s, e):
                if b > a and match(name):
                    yield name, (b - a) * 1e-9

    def op_seconds(self, match: Callable[[str], bool]) -> Tuple[float, int]:
        """Summed device time and count of the ops ``match`` accepts."""
        secs = [t for _, t in self.ops(match)]
        return sum(secs), len(secs)

    def program_seconds(self, match: Callable[[str], bool]
                        ) -> Tuple[float, int]:
        """Summed device time (window-clipped) and count of the program
        runs (``XLA Modules`` events) that hold an op ``match`` accepts:
        the whole device cost of the calls that use a kernel, the
        copies and reductions around it included."""
        tot, n = 0.0, 0
        for d in self.devices.values():
            hits = np.sort(np.asarray(
                [a for name, a in zip(d.names, d.start) if match(name)],
                np.float64))
            if not hits.size:
                continue
            for a, b, _ in d.modules or ():
                i = np.searchsorted(hits, a)
                if i < hits.size and hits[i] <= b:
                    a2, b2 = max(a, self.t0), min(b, self.t1)
                    if b2 > a2:
                        tot += b2 - a2
                        n += 1
        return tot * 1e-9, n

    def matching_ops(self, match: Callable[[str], bool]) -> List[str]:
        return [name for name, _ in self.ops(match)]

    # -- breakdown ------------------------------------------------------- #
    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` instructions that took most device time."""
        acc: Dict[str, float] = defaultdict(float)
        for name, secs in self.ops(lambda _: True):
            acc[op_name(name)] += secs
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v] for n, v in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """Device idle time in the window (first chip), split by what
        the host was doing: each stretch between span boundaries is
        labelled with the benchmark spans open in it (``bench.window``
        alone: none of the driver's own), and its idle time is its
        length less the device's busy time in it.  Largest first."""
        if not self.devices:
            return [[WINDOW_SPAN, self.window_s]]
        d = self.devices[sorted(self.devices)[0]]
        s, e = self._clip(d)
        busy = [(a, b) for a, b in _merge(s, e) if b > a]
        starts = np.asarray([a for a, _ in busy] or [self.t0])
        ends = np.asarray([b for _, b in busy] or [self.t0])
        cum = np.concatenate([[0.0], np.cumsum(ends - starts)])

        def busy_before(t: float) -> float:
            i = int(np.searchsorted(starts, t, side="right"))
            if i == 0:
                return 0.0
            return float(cum[i - 1] + min(t, ends[i - 1]) - starts[i - 1])

        events = [(self.t0, 0, ""), (self.t1, 0, "")]
        for a, b, name in self.spans:
            if name != WINDOW_SPAN and b > self.t0 and a < self.t1:
                events.append((max(a, self.t0), 1, name))
                events.append((min(b, self.t1), -1, name))
        events.sort()
        open_: Dict[str, int] = defaultdict(int)
        acc: Dict[str, float] = defaultdict(float)
        prev = self.t0
        for t, delta, name in events:
            if t > prev:
                label = "+".join(sorted(n for n, c in open_.items() if c)) \
                    or WINDOW_SPAN
                idle = (t - prev) - (busy_before(t) - busy_before(prev))
                acc[label] += idle * 1e-9
                prev = t
            if name:
                open_[name] += delta
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:k]
        return [[n, v] for n, v in top if v > 0]



def op_name(text: str) -> str:
    """``%name.12 = ...`` -> ``name.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def is_tpu_kernel(text: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in text


def operand_shapes(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    """(dtype, dims) of each operand of a custom-call instruction."""
    import re
    if " custom-call(" not in text:
        return []
    rest = text.split(" custom-call(", 1)[1]
    depth, end = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            end = i
            break
    args = re.sub(r"\{[^}]*\}", "", rest[:end])     # drop layouts
    out = []
    for m in re.finditer(r"([a-z0-9]+)\[([0-9,]*)\]", args):
        dims = tuple(int(x) for x in m.group(2).split(",") if x)
        out.append((m.group(1), dims))
    return out


def load(log_dir: str) -> Trace:
    return Trace.from_file(find_xplane(log_dir))

