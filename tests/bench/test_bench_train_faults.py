"""A training run with its step broken underneath comes out not
correct: a step that returns its state unchanged, half of the batch
left out (the mean taken over the rest), the loss altered where it is
produced, and the SSD decays (``A_log``) left unmoved."""

import pytest

from bench_tiny import run, train_cell


def _broken_step(kind):
    import repro.train.trainer as T
    orig = T.make_train_step

    def make(cfg, opt_cfg, journal=False):
        step = orig(cfg, opt_cfg, journal)

        def broken(state, batch):
            if kind == "half_batch":
                half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
                return step(state, half)
            new, metrics = step(state, batch)
            if kind == "unchanged":
                return state, metrics
            if kind == "decay_unmoved":
                return _keep_a_log(new, state), metrics
            return new, dict(metrics, loss=metrics["loss"] * 1.01)
        return broken
    return make


def _keep_a_log(new, old):
    import jax
    params = jax.tree_util.tree_map_with_path(
        lambda path, n, o: o if "A_log" in jax.tree_util.keystr(path)
        else n, new["params"], old["params"])
    return dict(new, params=params)


@pytest.mark.parametrize("kind", ["unchanged", "half_batch", "altered",
                                  "decay_unmoved"])
def test_step_faults_are_not_correct(kind, monkeypatch, tmp_path):
    import repro.train.trainer as T
    monkeypatch.setattr(T, "make_train_step", _broken_step(kind))
    out = run(train_cell(), 0.3, tmp_path)
    assert not out["correct"], out["checks"]
    if kind == "decay_unmoved":
        c = out["checks"]["own_change_gap"]
        assert c["value"] > c["limit"]
