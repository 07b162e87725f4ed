#!/usr/bin/env python3
"""A traced run of one cell, read down to the program's own spans.

Runs the cell as ``bench/run.py --trace 1`` does (set-up, the traced
window, the comparison) and prints its result line with one key more,
``program``:

  ``readings``              per-layer readings of the program's spans
                            and counters (``bench/lib/spans.py``)
  ``idle_by_program_span``  device idle seconds by the innermost
                            ``arcadia.*`` span open on each thread
  ``spans``                 name -> [seconds, self seconds, count] of
                            every program span in the window
  ``bench_spans``           name -> [seconds, count] of the benchmark's
                            own spans in the window
  ``counters``              window change of the program's counters
  ``end_to_end_traced``     the window's end-to-end numbers with the
                            profiler on (the cost of tracing: compare
                            with an untraced ``bench/run.py`` run)

    python bench/tools/span_report.py --workload wal-large.sync1 \\
        --seed 7 --seconds 51 [--keep-trace out.xplane.pb]

Like ``bench/run.py`` it needs the chip and exits 3 without one.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def program_counters(drv) -> dict:
    """The program's counters the readings use, where the driver holds
    an ingest engine or a replica set."""
    out = {}
    eng = getattr(drv, "engine", None)
    if eng is not None:
        out.update(eng.stats())
    rs = getattr(drv, "rs", None)
    if rs is not None:
        out.update(rs.log.stats())
    return out


def report(cell, seconds: float, devices, spec, t_start: float,
           trace_dir: str, keep: Optional[str] = None) -> dict:
    """``run_cell`` with the trace also read for program spans."""
    from bench import run
    from bench.lib import common, spans, trace

    got = {}
    load, load_module = trace.load, common.load_module

    def traced_load(log_dir):
        path = trace.find_xplane(log_dir)
        got["trace"] = t = trace.Trace.from_file(path)
        got["spans"] = spans.read_program_spans(path)
        if keep:
            shutil.copy(path, keep)
        return t

    def counted_load_module(kind, name):
        mod = load_module(kind, name)
        if kind == "drivers":
            base = mod.Driver

            class Driver(base):
                def window(self, seconds):
                    c0 = program_counters(self)
                    res = super().window(seconds)
                    got["counters"] = spans.counter_delta(
                        c0, program_counters(self))
                    got["end_to_end"] = dict(res.end_to_end)
                    return res
            mod.Driver = Driver
        return mod

    trace.load, common.load_module = traced_load, counted_load_module
    try:
        out = run.run_cell(cell, seconds, True, devices, spec, t_start,
                           trace_dir)
    finally:
        trace.load, common.load_module = load, load_module
    t = got["trace"]
    ps = spans.ProgramSpans(got["spans"], t.t0, t.t1)
    bench_spans = {}
    for a, b, name in t.spans:
        if name != trace.WINDOW_SPAN and b > t.t0 and a < t.t1:
            row = bench_spans.setdefault(name, [0.0, 0])
            row[0] += (min(b, t.t1) - max(a, t.t0)) * 1e-9
            row[1] += int(a >= t.t0)
    out["program"] = {
        "readings": spans.readings(ps, got["counters"]),
        "idle_by_program_span": spans.idle_by_program_span(
            t, got["spans"], 12),
        "spans": ps.totals(), "bench_spans": bench_spans,
        "counters": got["counters"], "end_to_end_traced": got["end_to_end"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--keep-trace", default=None,
                    help="copy the .xplane.pb here")
    args = ap.parse_args(argv)

    from bench import run
    from bench.lib import common
    cell = run.cell_for(args.workload, args.seed)
    spec = common.benchmark_spec(ROOT)
    try:
        devices = run.devices_for(int(cell.workload["chips"]))
    except run.NoChip as exc:
        print(f"bench/tools/span_report.py: {exc}", file=sys.stderr)
        return 3
    common.use_compile_cache(ROOT)
    out = report(cell, args.seconds, devices, spec, T_START,
                 os.path.join(ROOT, ".bench_trace", cell.name),
                 args.keep_trace)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
