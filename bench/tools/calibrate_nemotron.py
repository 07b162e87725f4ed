#!/usr/bin/env python3
"""Readings behind the limits of the Nemotron-H training cell's
comparison, and the save time behind its checkpoint period.

For each seed, in one process on the chip: the program's first three
steps through the cell's driver (its loss, first-gradient, parameter-
change and routed-row readings, and the wall time of a set-up step),
then the plain float32 reference.  For the seeds given to ``--faults``,
also the float8 control (the reference with every matrix product in
float8), the reference with half of the batch left out (``half_batch``)
and the program with each fault of ``--planted`` underneath: the
capacity-bound ``moe_ffn`` at capacity factor 1.0 in place of the
dropless layer (``capacity``), the routed gates left unscaled
(``unscaled``), and the Mamba-2 norm taken before the gate
(``norm_first``).  Prints one JSON line per seed.

``--save-time`` first times one checkpoint of the cell's state after
set-up: the state's copy to the host (fetch) and ``CheckpointManager.save``
of the fetched state (encode, CRC, store puts, manifest).

    python bench/tools/calibrate_nemotron.py \\
        --workload nemotron3-nano-30b-a3b-journal.train8k \\
        --seeds 11,12,13 --faults 11 --planted unscaled --save-time
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from dataclasses import replace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

FAULTS = ("capacity", "unscaled", "norm_first")


def plant(fault: str):
    """Plants ``fault`` in the program's layers; returns the undo."""
    from repro.models import layers as L
    name = "ssm_mixer" if fault == "norm_first" else "moe_held_ffn"
    orig = getattr(L, name)
    if fault == "capacity":
        def broken(x, p, cfg):
            return L.moe_ffn(x, p, replace(cfg, capacity_factor=1.0))
    elif fault == "unscaled":
        def broken(x, p, cfg):
            return orig(x, p, replace(cfg, routed_scaling=1.0))
    else:
        def broken(x, p, cfg, cache=None):
            return orig(x, p, replace(cfg, ssm_gate_first=False),
                        cache=cache)
    setattr(L, name, broken)
    return lambda: setattr(L, name, orig)


def program_readings(cell, save_time: bool = False):
    from bench.lib import common
    drv = common.load_module("drivers", cell.workload["driver"]).Driver(
        cell, common.SpanLog())
    t0 = time.perf_counter()
    drv.setup()
    out = {"setup_s": time.perf_counter() - t0,
           "setup_step_s": drv.step_wall_s}
    if save_time:
        import jax
        tr = drv.tr
        t0 = time.perf_counter()
        host = jax.device_get(tr.state)
        out["fetch_s"] = time.perf_counter() - t0
        nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(host))
        t0 = time.perf_counter()
        tr.mgr.save(int(host["step"]), host)
        out["write_s"] = time.perf_counter() - t0
        out["state_bytes"] = int(nbytes)
        del host
    readings = drv.readings
    drv.tr.state = None
    drv.tr.mgr.close()
    drv.rs.shutdown()
    del drv
    gc.collect()
    return readings, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="",
                    help="seeds that also run the control and the faults")
    ap.add_argument("--planted", default=",".join(FAULTS),
                    help="the program faults planted on those seeds")
    ap.add_argument("--save-time", action="store_true")
    args = ap.parse_args(argv)

    from bench.lib import common
    from bench.lib import nemotron_h_ref as ref
    from bench.run import cell_for
    common.use_compile_cache(ROOT)
    fault_seeds = {int(s) for s in args.faults.split(",") if s}
    planted = [f for f in args.planted.split(",") if f]
    assert set(planted) <= set(FAULTS), planted
    first = True
    for seed in [int(s) for s in args.seeds.split(",")]:
        cell = cell_for(args.workload, seed)
        prog, out = program_readings(cell, save_time=args.save_time and first)
        first = False
        p, model, opt = cell.params, cell.config, cell.config["optimizer"]
        t0 = time.perf_counter()
        r = ref.reference_run(model, opt, seed, p["batch"], p["seq"])
        out["reference_s"] = time.perf_counter() - t0
        small = ref.compared(r, r)["excluded"]
        out.update(seed=seed, program=ref.compared(prog, r),
                   small_leaves={n: {"grad_prog": prog["grad"][n],
                                     "grad_ref": r["grad"][n]}
                                 for n in small},
                   program_losses=prog["losses"], ref_losses=r["losses"],
                   rows=[prog["rows"], r["rows"]],
                   max_rows=[prog["max_rows"], r["max_rows"]])
        if seed in fault_seeds:
            c = ref.reference_run(model, opt, seed, p["batch"], p["seq"],
                                  precision="fp8")
            out["control_fp8"] = ref.compared(c, r)
            h = ref.reference_run(model, opt, seed, p["batch"] // 2,
                                  p["seq"])
            out["fault_half_batch"] = ref.compared(h, r)
            for fault in planted:
                undo = plant(fault)
                try:
                    f, _ = program_readings(cell)
                finally:
                    undo()
                out[f"fault_{fault}"] = ref.compared(f, r)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
