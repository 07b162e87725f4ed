#!/usr/bin/env python3
"""The WAL cells' controls on the chip, at the cell's own size, each of
which has to come out not correct.  ``backup_dropped``: one backup
dropped from replication after the replica set is built (the
three-copy guarantee broken).  ``quorum1``: the replica set built with
a write quorum of 1, so that an append is acknowledged once the
primary alone holds it (the ack guarantee broken).  Prints each run's
result line.

    python bench/tools/control_wal.py --workload wal-large.ingest16 \\
        --control quorum1 --seeds 1,2,3 --seconds 10
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def backup_dropped(build):
    def dropped(cfg, ingest=None):
        rs = build(cfg, ingest=ingest)
        rs.fail_backup(rs.servers[-1].server_id)
        return rs
    return dropped


def quorum1(build):
    def primary_alone(cfg, ingest=None):
        rep = dict(cfg["replication"], write_quorum=1)
        return build(dict(cfg, replication=rep), ingest=ingest)
    return primary_alone


CONTROLS = {"backup_dropped": backup_dropped, "quorum1": quorum1}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", choices=("backup_dropped", "quorum1"),
                    default="backup_dropped")
    args = ap.parse_args(argv)

    from bench.lib import common, wal
    from bench.run import cell_for, devices_for, run_cell
    wal.build = CONTROLS[args.control](wal.build)
    spec = common.benchmark_spec(ROOT)
    common.use_compile_cache(ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = cell_for(args.workload, seed)
        devices = devices_for(int(cell.workload["chips"]))
        out = run_cell(cell, args.seconds, False, devices, spec,
                       time.perf_counter(),
                       os.path.join(ROOT, ".bench_trace", "control"))
        print(json.dumps({"seed": seed, "control": args.control, **out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
