"""The training driver's control flow at a tiny size on the CPU, called
directly, and the plain reference against the program."""

import pytest

from bench_tiny import SEED, run, train_cell


def test_train_cell_runs_and_is_correct(tmp_path):
    out = run(train_cell(), 0.3, tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    c = out["checks"]
    # float32 compute at this size: the program and the reference agree
    # to round-off
    assert c["loss_gap"]["value"] < 1e-5
    assert c["grad_gap"]["value"] < 1e-4
    assert c["restored_leaves_differ"]["value"] == 0
    assert c["journal_losses_missing"]["value"] == 0


def test_window_ends_on_a_checkpoint():
    from bench.lib.common import SpanLog, load_module
    cell = train_cell()
    drv = load_module("drivers", "train").Driver(cell, SpanLog())
    drv.start_step = 3
    n = cell.params["ckpt_every"]
    for secs in (0.01, 0.2, 1.0, 3.0):
        k = drv.window_steps(secs)
        assert k > 0 and (3 + k) % n == 0


def test_control_fails_the_limits():
    """The float8 control, in the program's place, against the float32
    reference: at least one number over its limit."""
    from bench.lib import mamba2_ref as ref
    cell = train_cell()
    m, o, p = cell.config["model"], cell.config["optimizer"], cell.params
    r = ref.reference_run(m, o, SEED, p["batch"], p["seq"])
    c = ref.compared(ref.reference_run(m, o, SEED, p["batch"], p["seq"],
                                       precision="fp8"), r)
    lim = cell.config["limits"]
    assert any(c[k] > lim[k] for k in lim), (c, lim)
