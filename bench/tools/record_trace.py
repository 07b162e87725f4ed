#!/usr/bin/env python3
"""Record a small profiler trace of the benchmark's device paths.

Runs, under ``jax.profiler`` and inside a ``bench.window`` span, one
append-path hash call (a 1 MiB record through ``kernels/checksum``), one
small SSD forward (``kernels/ssd_scan``) and a matmul, each inside a
benchmark host span, then prints the planes, lines and event names the
trace holds and copies the ``.xplane.pb`` to ``--out``.  The committed test trace under ``tests/bench/data`` was made
with it:

    python bench/tools/record_trace.py --out trace_probe
"""

from __future__ import annotations

import argparse
import glob
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.kernels.checksum.ops import tensor_checksum_batch
    from repro.kernels.ssd_scan import ops as ssd

    rng = np.random.default_rng(0)
    mat = rng.integers(0, 2**32, (1, (1 << 18) + 3), dtype=np.uint32)
    B, S, H, P, N = 1, 512, 24, 64, 128
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(0.001, 0.1, (B, S, H)), jnp.float32)
    a_log = jnp.zeros((H,), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.bfloat16)
    cm = jnp.asarray(rng.normal(size=(B, S, 1, N)), jnp.bfloat16)
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    mm = jax.jit(lambda a: a @ a)
    ssd_fn = jax.jit(ssd.ssd, static_argnames="chunk")
    # warm up outside the trace
    tensor_checksum_batch(mat)
    jax.block_until_ready(ssd_fn(xh, dt, a_log, bm, cm, chunk=256))
    mm(x).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    tmp = tempfile.mkdtemp(dir=os.environ.get("TMPDIR"))
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("bench.append"):
            tensor_checksum_batch(mat)
        with jax.profiler.TraceAnnotation("bench.train_step"):
            jax.block_until_ready(ssd_fn(xh, dt, a_log, bm, cm, chunk=256))
            mm(x).block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                     recursive=True)[0]
    os.makedirs(args.out, exist_ok=True)
    shutil.copy(path, os.path.join(args.out, "probe.xplane.pb"))
    shutil.rmtree(tmp, ignore_errors=True)

    from jax.profiler import ProfileData
    pd = ProfileData.from_file(os.path.join(args.out, "probe.xplane.pb"))
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r} lines={len(lines)}")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r} events={len(evs)}")
            for ev in evs[:25]:
                print(f"    {ev.name[:100]!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} "
                      f"stats={[(k, str(v)[:60]) for k, v in ev.stats][:6]}")
    f8 = jnp.float8_e4m3fn
    y = jax.jit(lambda a: jnp.dot(a.astype(f8), a.astype(f8),
                                  preferred_element_type=jnp.float32))(x)
    print("fp8 dot ok", float(y[0, 0]))
    print("device", jax.devices()[0].device_kind,
          os.path.getsize(os.path.join(args.out, "probe.xplane.pb")))
    return 0


if __name__ == "__main__":
    sys.exit(main())
