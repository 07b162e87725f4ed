"""Every cell, configuration and metric of BENCHMARK.json is a file of
its own, found by name, and ``bench/run.py`` refuses a host without a
TPU."""

import json
import os
import subprocess
import sys

import pytest

from bench_tiny import ROOT
from bench.lib import common

SPEC = common.benchmark_spec(ROOT)


def test_paths_and_command():
    assert SPEC["command"] == ["python", "bench/run.py"]
    for p in SPEC["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_loads_by_name(cfg):
    assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
    data = common.load_config(cfg["name"])
    assert data["name"] == cfg["name"]
    assert data["reduced"] == cfg["reduced"]
    assert "assumed" in data and "guarantees" in data


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_workload_file_loads_by_name(wl):
    data = common.load_workload(wl["name"])
    assert data["config"] == wl["config"]
    assert wl["name"] == f"{wl['config']}.{wl['traffic']}"
    assert data["chips"] == wl["chips"]
    assert data["why"] == wl["why"]
    drv = common.load_module("drivers", data["driver"])
    assert hasattr(drv, "Driver")


@pytest.mark.parametrize("m", SPEC["per_layer"], ids=lambda m: m["name"])
def test_metric_file_loads_by_name(m):
    mod = common.load_module("metrics", m["name"])
    assert callable(mod.read)
    cells = {w["name"] for w in SPEC["workloads"]}
    assert set(m["workloads"]) <= cells
    # a reader that finds nothing to read returns nothing
    assert mod.read({"trace": None, "counters": {}, "spans": {},
                     "peaks": None, "devices": None,
                     "cell": None}) is None


def test_every_cell_reports_setup_and_another_metric():
    for wl in SPEC["workloads"]:
        e2e = [m["name"] for m in SPEC["end_to_end"]
               if wl["name"] in m.get("workloads", [wl["name"]])]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert any(wl["name"] in m["workloads"] for m in SPEC["per_layer"])


def test_unknown_names_are_errors():
    with pytest.raises(FileNotFoundError):
        common.load_module("metrics", "no_such_metric")
    with pytest.raises(FileNotFoundError):
        common.load_workload("no_such.cell")


def test_run_refuses_a_host_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "wal-large.sync1", "--seed", str(2**31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr
