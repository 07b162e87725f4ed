"""The trace reduction on a small trace recorded on a TPU v5e by
``bench/tools/record_trace.py``: one append-path hash call, one SSD
forward and a matmul inside ``bench.window``."""

import os

import pytest

from bench_tiny import ROOT  # noqa: F401  (puts bench/ on the path)
from bench.lib import readers, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "probe.xplane.pb")
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9}


@pytest.fixture(scope="module")
def tr():
    return trace.Trace.from_file(DATA)


def test_window_and_busy(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    assert tr.window_s == pytest.approx(0.00432092)
    # union of the op intervals: hash program, SSD program, matmul
    assert tr.busy_s() == pytest.approx(7.3692e-05)
    assert 0 < tr.busy_s() < tr.window_s


def test_kernels_found_by_signature(tr):
    names = [trace.op_name(n) for n in tr.matching_ops(trace.is_tpu_kernel)]
    assert names == ["_hash_rows.1", "ssd.1"]
    assert [trace.op_name(n) for n in
            tr.matching_ops(readers.is_hash_kernel)] == ["_hash_rows.1"]
    assert [trace.op_name(n) for n in
            tr.matching_ops(readers.is_ssd_kernel)] == ["ssd.1"]
    ssd = tr.matching_ops(readers.is_ssd_kernel)[0]
    assert trace.operand_shapes(ssd) == [
        ("f32", (1, 24, 2, 256, 64)), ("f32", (1, 24, 2, 256, 1)),
        ("f32", (1, 24, 2, 1, 256)), ("f32", (1, 1, 2, 256, 128)),
        ("f32", (1, 1, 2, 256, 128))]


def test_kernel_and_program_time(tr):
    k, n = tr.op_seconds(readers.is_hash_kernel)
    assert n == 1 and k == pytest.approx(5.38e-07)
    prog, n = tr.program_seconds(readers.is_hash_kernel)
    assert n == 1 and prog == pytest.approx(6.05e-06)
    assert prog > k                 # the copy into VMEM is part of the call


def test_hash_roofline_stays_under_the_peak(tr):
    # the kernel alone reads 1 MiB faster than HBM could deliver it;
    # the whole hash program does not
    need = 12 + (1 << 20)
    assert need / PEAKS["hbm_bytes_s"] / 5.38e-07 > 1.0
    share = readers.hash_roofline(
        {"trace": tr, "counters": {"hashed_bytes": need}, "peaks": PEAKS})
    assert share == pytest.approx(100 * need / 819e9 / 6.05e-06)
    assert 0 < share < 100


def test_ssd_roofline_and_idle(tr):
    share, bound = readers.ssd_roofline({"trace": tr, "peaks": PEAKS})
    # f32 operands, C·Bᵀ counted once per group: 157 FLOP a byte, under
    # the v5e's 240, so the bytes bound
    assert bound == "memory" and 0 < share < 100
    idle = readers.device_idle({"trace": tr})
    assert idle == pytest.approx(100 * (1 - 7.3692e-05 / 0.00432092))


def test_breakdown(tr):
    top = tr.top_ops(3)
    assert [t[0] for t in top] == ["ssd.1", "fusion", "reshape.12"]
    gaps = dict(tr.idle_gaps())
    assert set(gaps) == {"bench.append", "bench.train_step", "bench.window"}
    assert sum(gaps.values()) == pytest.approx(tr.window_s - tr.busy_s())


def test_idle_split_by_open_spans_by_hand():
    """Window [0, 100] ns, busy [10, 20] and [50, 60]; span A [0, 40],
    span B [30, 70]."""
    import numpy as np
    ops = trace.DeviceOps(["%a = op", "%b = op"], np.array([10.0, 50.0]),
                          np.array([20.0, 60.0]), [])
    t = trace.Trace({"/device:TPU:0": ops},
                    [(0.0, 100.0, trace.WINDOW_SPAN), (0.0, 40.0, "bench.A"),
                     (30.0, 70.0, "bench.B")])
    gaps = {n: v * 1e9 for n, v in t.idle_gaps()}
    assert gaps == pytest.approx({"bench.A": 20.0, "bench.A+bench.B": 10.0,
                                  "bench.B": 20.0, "bench.window": 30.0})
    assert t.busy_s() == pytest.approx(20e-9)


def test_no_window_span_is_an_error(tr):
    with pytest.raises(ValueError):
        trace.Trace(tr.devices, [s for s in tr.spans
                                 if s[2] != trace.WINDOW_SPAN])
