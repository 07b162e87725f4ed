#!/usr/bin/env python3
"""Readings behind the limits of a training cell's comparison.

For each seed, in one process on the chip: the program's first three
steps through the cell's driver (its loss, first-gradient and
parameter-change readings), then the plain float32 reference, the
float8 control, and the reference with half of the batch left out (the
half-batch fault).  Prints one JSON line per seed with the numbers
compared for the program, the control and the fault, and the gradient
and change norms of the leaves that ``change_gap`` leaves out.

    python bench/tools/calibrate_train.py --workload mamba2-130m-journal.train \\
        --seeds 11,12,13
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--skip-control", action="store_true")
    args = ap.parse_args(argv)

    from bench.lib import common
    from bench.lib import mamba2_ref as ref
    from bench.run import cell_for
    common.use_compile_cache(ROOT)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        cell = cell_for(args.workload, seed)
        drv = common.load_module("drivers", cell.workload["driver"]).Driver(
            cell, common.SpanLog())
        drv.setup()
        prog = drv.readings
        drv.tr.state = None
        drv.tr.mgr.close()
        drv.rs.shutdown()
        del drv
        gc.collect()
        p, model, opt = cell.params, cell.config["model"], \
            cell.config["optimizer"]
        t1 = time.perf_counter()
        r = ref.reference_run(model, opt, seed, p["batch"], p["seq"])
        t2 = time.perf_counter()
        small = ref.compared(r, r)["excluded"]
        out = {"seed": seed, "program": ref.compared(prog, r),
               "small_leaves": {n: {"grad_prog": prog["grad"][n],
                                    "grad_ref": r["grad"][n],
                                    "change_prog": prog["change"][n],
                                    "change_ref": r["change"][n]}
                                for n in small},
               "program_losses": prog["losses"], "ref_losses": r["losses"],
               "setup_s": t1 - t0, "reference_s": t2 - t1}
        if not args.skip_control:
            c = ref.reference_run(model, opt, seed, p["batch"], p["seq"],
                                  precision="fp8")
            out["control_fp8"] = ref.compared(c, r)
            h = ref.reference_run(model, opt, seed, p["batch"] // 2,
                                  p["seq"])
            out["fault_half_batch"] = ref.compared(h, r)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
