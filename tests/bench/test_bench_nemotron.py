"""The Nemotron-H training cell at a tiny size on the CPU: the driver
runs through ``Trainer.run`` and is correct; each fault planted in the
program (half of the batch left out among them) and the float8 control
come out not correct; the readers find
the grouped-product kernel by its operands and count by hand."""

import pytest

from nemotron_tiny import SEED, nemotron_cell, tiny_model
from bench_tiny import run


def test_cell_runs_and_is_correct(tmp_path):
    out = run(nemotron_cell(), 0.3, tmp_path)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"]["train_tokens_per_s"]["value"] > 0
    c = out["checks"]
    # float32 compute at this size: the program and the reference agree
    # to round-off and route every row alike
    assert c["loss_gap"]["value"] < 1e-5
    assert c["grad_gap"]["value"] < 1e-4
    assert c["rows_gap"]["value"] == 0
    assert c["restored_leaves_differ"]["value"] == 0
    assert c["journal_losses_missing"]["value"] == 0


def test_window_counts_saves_and_rows():
    from bench.lib.common import SpanLog, load_module
    cell = nemotron_cell()
    drv = load_module("drivers", "train_nemotron").Driver(cell, SpanLog())
    drv.setup()
    try:
        assert drv.readings["rows"] > 0
        res = drv.window(0.3)
        c = res.counters
        n = cell.params["ckpt_every"]
        assert (drv.start_step + c["steps"]) % n == 0
        assert c["ckpts_saved"] == c["saves"] >= 1
        assert c["ckpts_skipped"] == 0
        # per expert layer and step the largest held expert has at least
        # the mean and at most all of the held rows
        held = cell.config["n_routed_experts"]
        assert c["moe_rows"] <= c["moe_max_rows"] * held
        assert c["moe_max_rows"] <= c["moe_rows"]
    finally:
        drv.free()
        drv.tr.mgr.close()
        drv.rs.shutdown()


@pytest.mark.parametrize("fault", ["capacity", "unscaled", "norm_first"])
def test_planted_faults_are_not_correct(fault, tmp_path):
    from bench.tools.calibrate_nemotron import plant
    undo = plant(fault)
    try:
        out = run(nemotron_cell(), 0.3, tmp_path)
    finally:
        undo()
    assert not out["correct"], out["checks"]
    if fault == "capacity":
        c = out["checks"]["rows_gap"]
        assert c["value"] > c["limit"]


def test_half_of_the_batch_left_out_is_not_correct(monkeypatch, tmp_path):
    import repro.train.trainer as T
    orig = T.make_train_step

    def make(cfg, opt_cfg, *a):
        step = orig(cfg, opt_cfg, *a)
        return lambda state, batch: step(
            state, {k: v[:v.shape[0] // 2] for k, v in batch.items()})

    monkeypatch.setattr(T, "make_train_step", make)
    out = run(nemotron_cell(), 0.3, tmp_path)
    assert not out["correct"], out["checks"]
    c = out["checks"]["change_gap"]
    assert c["value"] > c["limit"]


def test_control_fails_the_limits():
    """The float8 control, in the program's place, against the float32
    reference: at least one number over its limit."""
    from bench.lib import nemotron_h_ref as ref
    cell = nemotron_cell()
    m, o, p = tiny_model(), cell.config["optimizer"], cell.params
    r = ref.reference_run(m, o, SEED, p["batch"], p["seq"])
    c = ref.compared(ref.reference_run(m, o, SEED, p["batch"], p["seq"],
                                       precision="fp8"), r)
    lim = cell.config["limits"]
    assert any(c[k] > lim[k] for k in lim), (c, lim)


GMM = ('%gmm.3 = bf16[98304,2688]{1,0} custom-call(s32[] %a, s32[9]{0} %b, '
       's32[199]{0} %c, s32[199]{0} %d, s32[1]{0} %e, '
       'bf16[98304,1856]{1,0} %f, bf16[8,1856,2688]{2,1,0} %g), '
       'custom_call_target="tpu_custom_call"')
TGMM = ('%tgmm = bf16[8,2688,1856]{2,1,0} custom-call(s32[] %a, '
        's32[9]{0} %b, s32[199]{0} %c, s32[199]{0} %d, s32[1]{0} %e, '
        'bf16[98304,2688]{1,0} %f, bf16[98304,1856]{1,0} %g), '
        'custom_call_target="tpu_custom_call"')
SSD = ('%k = f32[1] custom-call(f32[2,64,64,128,64]{4,3,2,1,0} %a, '
       'f32[2,64,64,128,1]{4,3,2,1,0} %b, f32[2,64,64,1,128]{4,3,2,1,0} %c, '
       'f32[2,8,64,128,128]{4,3,2,1,0} %d, f32[2,8,64,128,128]{4,3,2,1,0} '
       '%e), custom_call_target="tpu_custom_call"')


@pytest.mark.parametrize("text,want", [(GMM, True), (TGMM, True),
                                       (SSD, False),
                                       (GMM.replace("tpu_custom_call",
                                                    "other"), False)])
def test_gmm_kernel_found_by_operands(text, want):
    from bench.lib.nemotron import is_gmm_kernel
    assert is_gmm_kernel(text) is want


def test_gmm_need_and_model_flops_by_hand():
    from bench.lib import nemotron as nm
    from bench.run import cell_for
    cfg = cell_for("nemotron3-nano-30b-a3b-journal.train8k", SEED).config
    D, F = 2688, 1856
    flops, nbytes = nm.gmm_need(cfg, rows=6144, layer_steps=1)
    assert flops == 12 * D * F * 6144
    assert nbytes == 6 * 2 * (6144 * (D + F) + 8 * D * F)
    di, GN = 4096, 8 * 128
    mamba = (2 * D * (2 * di + 2 * GN + 64) + 2 * 4 * (di + 2 * GN)
             + 2 * 128 * 128 * 8 + 64 * (2 * 128 * 64 + 4 * 128 * 64)
             + 2 * di * D)
    experts = 2 * D * 128 + 4 * D * 3712
    attn = 2 * D * (32 + 4) * 128 + 2 * 4096 * D + 2 * 4096 * 8192
    dense = 3 * mamba + 3 * experts + attn + 2 * D * 16384
    assert nm.dense_flops_per_token(cfg, 8192) == dense
    assert nm.window_model_flops(cfg, 8192, tokens=10, rows=7) == \
        3 * (10 * dense + 7 * 4 * D * F)


def test_readers_return_nothing_without_counters():
    from bench.lib import nemotron as nm
    ctx = {"trace": None, "counters": {}, "spans": {}, "peaks": None,
           "devices": None, "cell": nemotron_cell()}
    assert nm.gmm_roofline(ctx) is None
    assert nm.train_mfu(ctx) is None
    assert nm.load_imbalance(ctx) is None
    ctx["counters"] = {"moe_rows": 800, "moe_max_rows": 300}
    held = ctx["cell"].config["n_routed_experts"]
    assert nm.load_imbalance(ctx) == 300 * held / 800
