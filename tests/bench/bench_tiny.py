"""Tiny versions of the benchmark's cells, for driving them on the CPU."""

from __future__ import annotations

import copy
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench.lib import common  # noqa: E402
from bench.run import cell_for, run_cell  # noqa: E402

MIB = 1 << 20
SEED = 2**31 + 17


def wal_cell(name: str, seed: int = SEED):
    c = cell_for(name, seed)
    c.config = copy.deepcopy(c.config)
    c.workload = copy.deepcopy(c.workload)
    c.config["ring_bytes"] = 24 * MIB
    c.config["records"] = {"min_bytes": MIB, "max_bytes": 2 * MIB,
                           "n_sizes": 4}
    p = c.params
    p["pool_bytes"] = 4 * MIB
    if "prefill_bytes" in p:
        p["prefill_bytes"] = 8 * MIB
        p["producers"] = min(p["producers"], 2)
    return c


def train_cell(seed: int = SEED):
    c = cell_for("mamba2-130m-journal.train", seed)
    c.config = copy.deepcopy(c.config)
    c.workload = copy.deepcopy(c.workload)
    c.config["model"].update(n_layer=1, d_model=128, vocab_size=512,
                             d_state=32, headdim=16, chunk_size=32,
                             compute_dtype="float32")
    c.params.update(batch=2, seq=64, ckpt_every=4, step_s=0.05,
                    reduced=True)
    return c


def run(cell, seconds: float, tmp) -> dict:
    return run_cell(cell, seconds, False, None, common.benchmark_spec(),
                    time.perf_counter(), str(tmp))
