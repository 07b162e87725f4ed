#!/usr/bin/env python3
"""Record a small profiler trace of the program's own spans.

Builds the ``wal-large`` replica set on a 24 MiB ring and, under
``jax.profiler`` and inside a ``bench.window`` span, appends a few
records through one producer of the ingest engine (as
``wal-large.sync1`` does, inside ``bench.append`` and
``bench.wait_ack``) and opens one crash image of the primary (inside
``bench.log_open``).  A first replica set, outside the trace, does the
same, so that nothing compiles in it.  Prints the program spans the
trace holds and copies the ``.xplane.pb`` to ``--out``.  The committed
``tests/bench/data/spans.xplane.pb`` was made with it:

    python bench/tools/record_spans.py --out trace_spans
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

MIB = 1 << 20
SIZES = [MIB, 2 * MIB, MIB + MIB // 2, 2 * MIB - 8]


def run_once(cfg, payloads, span) -> int:
    """Appends through the engine, then one open of a crash image;
    returns the opened log's durable LSN."""
    from repro.core import Log
    from bench.lib import wal
    rs = wal.build(cfg, ingest={"queue_records": 1024,
                                "queue_bytes": 64 * MIB,
                                "flush_records": 512,
                                "flush_bytes": 64 * MIB})
    try:
        for data in payloads:
            with span("bench.append"):
                ticket = rs.ingest.append(data)
            with span("bench.wait_ack"):
                ticket.wait(timeout=120)
        rs.ingest.drain()
        rs.group.drain()
        with span("bench.crash_image"):
            img = rs.primary_dev.crash()
        with span("bench.log_open"):
            log = Log.open(img, rs.cfg, repl=rs.group)
        return log.durable_lsn
    finally:
        rs.shutdown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    from bench.lib import common
    from bench.lib.spans import read_program_spans

    cfg = dict(common.load_config("wal-large"), ring_bytes=24 * MIB)
    rng = np.random.default_rng(0)
    payloads = [rng.bytes(n) for n in SIZES]
    span = jax.profiler.TraceAnnotation
    run_once(cfg, payloads, span)          # warm-up: compiles every shape

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with span("bench.window"):
        lsn = run_once(cfg, payloads, span)
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(args.out, exist_ok=True)
    out = os.path.join(args.out, "spans.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(tmp, ignore_errors=True)

    spans = read_program_spans(out)
    for name, n in sorted(Counter(s[2] for s in spans).items()):
        print(f"{name} {n}")
    print("opened durable_lsn", lsn, "of", len(SIZES))
    print("device", jax.devices()[0].device_kind, os.path.getsize(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
