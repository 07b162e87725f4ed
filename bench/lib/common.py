"""Harness plumbing shared by ``bench/run.py``, the drivers and the tests.

Everything that belongs to one cell, configuration or per-layer metric
lives in a file of its own and is found here by name:

  bench/workloads/<cell>.json   config name, driver name, traffic
  bench/configs/<config>.json   the deployment or model, with its source
  bench/drivers/<driver>.py     class ``Driver`` (setup / window / check)
  bench/metrics/<metric>.py     function ``read(ctx)`` -> float | None
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import time
from contextlib import contextmanager
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def benchmark_spec(root: str = ROOT) -> Dict[str, Any]:
    return load_json(root, "BENCHMARK.json")


def load_workload(name: str) -> Dict[str, Any]:
    return load_json(BENCH, "workloads", f"{name}.json")


def load_config(name: str) -> Dict[str, Any]:
    return load_json(BENCH, "configs", f"{name}.json")


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_words(seed: int, n: int = 4) -> np.ndarray:
    """``n`` uint32 words drawn from any whole-number seed."""
    return np.random.SeedSequence(int(seed)).generate_state(n)


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed),
                                                         *stream]))


def nearest_rank(values, pct: float) -> float:
    s = sorted(values)
    if not s:
        return float("nan")
    return s[max(0, min(len(s) - 1, math.ceil(pct / 100.0 * len(s)) - 1))]


@dataclass
class Cell:
    """One benchmark cell as the drivers see it."""
    name: str
    workload: Dict[str, Any]
    config: Dict[str, Any]
    seed: int

    @property
    def params(self) -> Dict[str, Any]:
        return self.workload["params"]


@dataclass
class Check:
    """One number compared for ``correct``: passes while value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(self.value <= self.limit)


@dataclass
class WindowResult:
    end_to_end: Dict[str, float]
    attempted: int
    failed: int
    counters: Dict[str, Any] = field(default_factory=dict)


@dataclass
class SpanLog:
    """Host spans the benchmark opens around calls into each layer.

    Each span is also a ``jax.profiler.TraceAnnotation`` so that the
    trace reduction can label device idle gaps with it; durations are
    kept here by name for metrics that read the benchmark's own spans.
    """
    durations: Dict[str, List[float]] = field(default_factory=dict)

    @contextmanager
    def span(self, name: str, keep: bool = False):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        if keep:
            self.durations.setdefault(name, []).append(
                time.perf_counter() - t0)


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, and how often
    it compiled a program for the backend."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.total = 0.0
        self.backend_compiles = 0

    def __call__(self, event, duration, **_):
        if event in self.EVENTS:
            self.total += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1


def peak_bytes(devices) -> Optional[int]:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def use_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at the fixed ``<root>/.jax_cache``
    (the path is part of the cache key).  The program's own cache helper
    reads ``JAX_COMPILATION_CACHE_DIR``, so it is pointed there too."""
    import jax
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
