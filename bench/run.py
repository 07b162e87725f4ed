#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is ``bench/workloads/<cell>.json``; it names its configuration
(``bench/configs/``) and its driver (``bench/drivers/``).  Set-up builds
the cell from the seed and warms up every shape it uses; the window then
runs for ``--seconds``.  With ``--trace 0`` the result holds the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read by
``bench/metrics/<metric>.py`` from a profiler trace of the window and the
driver's counters.  After the window the driver compares what the timed
path produced with the plain reference; every number compared is printed
beside its limit, on standard error and as the last key of the result.

Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, ROOT)

from bench.lib import common  # noqa: E402
from bench.lib.common import Cell  # noqa: E402


class NoChip(RuntimeError):
    pass


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def cell_for(name: str, seed: int) -> Cell:
    wl = common.load_workload(name)
    return Cell(name=name, workload=wl,
                config=common.load_config(wl["config"]), seed=seed)


def devices_for(chips: int):
    """The chips of this host; refuses anything but a TPU with enough."""
    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        raise NoChip(f"needs a TPU; JAX found {backend!r}")
    devs = jax.devices()
    if len(devs) < chips:
        raise NoChip(f"cell asks for {chips} chips; JAX found {len(devs)}")
    return devs[:chips]


def metric_specs(spec, cell_name: str, kind: str):
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in spec[kind]
            if cell_name in m.get("workloads", [cell_name])]


def run_cell(cell: Cell, seconds: float, trace: bool, devices,
             spec, t_start: float, trace_dir: str) -> dict:
    """Set up, measure and check one cell; returns the result object.
    ``devices`` is None only where a test drives a cell on the CPU."""
    import jax
    from bench.lib import trace as tr

    clock = common.CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    spans = common.SpanLog()
    drv = common.load_module("drivers", cell.workload["driver"]).Driver(
        cell, spans)
    drv.setup()
    compiles0 = clock.backend_compiles
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    setup_s = time.perf_counter() - t_start
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        res = drv.window(seconds)
    window_compiles = clock.backend_compiles - compiles0
    trace_obj = None
    if trace:
        jax.profiler.stop_trace()
        trace_obj = tr.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
    drv.free()
    peak = common.peak_bytes(devices) if devices else None

    metrics = {}
    if trace:
        ctx = {"cell": cell, "trace": trace_obj, "counters": res.counters,
               "spans": spans.durations, "peaks": _peaks(devices), "devices": devices}
        for m in metric_specs(spec, cell.name, "per_layer"):
            v = common.load_module("metrics", m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e = dict(res.end_to_end, setup_s=setup_s)
        for m in metric_specs(spec, cell.name, "end_to_end"):
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}

    try:
        checks = drv.check()
    except Exception as exc:             # a comparison that cannot run
        print(f"bench/run.py: comparison raised {exc!r}", file=sys.stderr)
        checks = [common.Check("comparison_raised", 1, 0)]
    checks.append(common.Check("compiles_in_window", window_compiles, 0))
    drv.close()
    del drv
    gc.collect()
    out = {"correct": all(c.ok for c in checks),
           "attempted": int(res.attempted), "failed": int(res.failed),
           "metrics": metrics}
    if devices:
        d0 = devices[0]
        out["device"] = {"platform": d0.platform, "kind": d0.device_kind,
                         "count": len(devices), "memory_peak_bytes": peak}
    else:
        out["device"] = None
    if trace_obj is not None:
        out["device"].update(busy_s=trace_obj.busy_s(),
                             window_s=trace_obj.window_s)
        out["breakdown"] = {"device_ops": trace_obj.top_ops(10),
                            "idle_gaps": trace_obj.idle_gaps(10)}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def _peaks(devices):
    from bench.lib.peaks import peaks_for
    return peaks_for(devices[0].device_kind) if devices else None


def main(argv=None) -> int:
    args = parse_args(argv)
    cell = cell_for(args.workload, args.seed)
    spec = common.benchmark_spec(ROOT)
    try:
        devices = devices_for(int(cell.workload["chips"]))
    except NoChip as exc:
        print(f"bench/run.py: {exc}", file=sys.stderr)
        return 3
    common.use_compile_cache(ROOT)
    out = run_cell(cell, args.seconds, bool(args.trace), devices, spec,
                   T_START, os.path.join(ROOT, ".bench_trace", cell.name))
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
