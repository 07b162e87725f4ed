"""The Nemotron-H stage in the program against the plain reference
(``bench/lib/nemotron_h_ref.py``) at a tiny size on seeded weights:
loss and gradients, dropless routing under a planted imbalance, the
expert shares adding up to the uncut layer; the mamba2 preset's mixer
unchanged; the grouped-product kernel; a trainer that donates its
state."""

import hashlib
import os
import sys
from dataclasses import replace

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "bench"))
from nemotron_tiny import tiny_model  # noqa: E402

from repro.configs import get_config, reduced_config  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import model as M  # noqa: E402
from bench.lib import nemotron_h_ref as ref  # noqa: E402

NAME = "nemotron3-nano-30b-a3b"
SEED = 2**31 + 101


def _batch(cfg, seed, batch=2, seq=32):
    rng = np.random.default_rng(seed)
    toks = rng.integers(0, cfg.vocab_size, (batch, seq), dtype=np.int32)
    labels = np.roll(toks, -1, axis=1)
    labels[:, -1] = -1
    return toks, labels


def test_preset_is_the_published_block_cut_to_one_stage():
    cfg = get_config(NAME)
    assert cfg.layer_pattern == "MEMEM*E" and cfg.n_layers == 7
    assert cfg.n_experts == 128 and cfg.experts_held == 8
    assert cfg.vocab_size == 131072 // 8
    assert L.ssm_dims(cfg) == (4096, 64, 64, 128)
    # the published widths: 38.7 M a Mamba-2 layer, 100.1 M an expert
    # layer with 8 experts held, 23.4 M the attention layer, 88.1 M the
    # embedding and head slices
    assert 527.5e6 < cfg.param_count() < 528.5e6
    red = reduced_config(NAME)
    assert red.name != cfg.name and red.layer_pattern == cfg.layer_pattern


def test_program_matches_reference_loss_and_gradients():
    cfg = reduced_config(NAME)
    model = tiny_model()
    params = ref.make_params(model, SEED)
    toks, labels = _batch(cfg, SEED)
    (loss, metrics), g = jax.jit(jax.value_and_grad(
        lambda p: M.forward_train(p, cfg, {"tokens": toks,
                                           "labels": labels}),
        has_aux=True))(params)
    f = ref.loss_and_grad_fn(model, "f32")
    tot = cnt = 0.0
    gref = jax.tree_util.tree_map(jnp.zeros_like, params)
    rows = 0
    with jax.default_matmul_precision("highest"):
        for r in range(toks.shape[0]):
            s, n, counts, gref = f(params, toks[r], labels[r], gref)
            tot, cnt = tot + float(s), cnt + float(n)
            rows += int(np.asarray(counts).sum())
    np.testing.assert_allclose(float(loss), tot / cnt, rtol=1e-5)
    assert int(metrics["moe_rows"]) == rows
    flat_p = jax.tree_util.tree_leaves_with_path(g)
    flat_r = jax.tree_util.tree_leaves(gref)
    for (path, a), b in zip(flat_p, flat_r):
        b = np.asarray(b) / cnt
        scale = max(float(np.abs(b).max()), 1e-12)
        assert float(np.abs(np.asarray(a) - b).max()) <= 1e-4 * scale, \
            jax.tree_util.keystr(path)


def _layer(cfg, model, seed):
    """The first expert layer's parameters of seeded weights."""
    p = ref.make_params(model, seed)["blocks"]["l1"]
    return jax.tree_util.tree_map(lambda x: x[0], p)


def _ref_layer(h, p, model):
    k = ref.dims(model)
    mm = ref.matmul("f32")
    with jax.default_matmul_precision("highest"):
        outs = [ref._experts(h[r], p, k, mm) for r in range(h.shape[0])]
    return (jnp.stack([o for o, _ in outs]),
            np.sum([np.asarray(c) for _, c in outs], axis=0))


@pytest.mark.parametrize("score", ["sigmoid", "softmax"])
def test_router_gates_are_top_k_scores_at_any_matmul_precision(score):
    """The gates are the top-k scores, renormalised and scaled, even
    where float32 products default to one bfloat16 pass (the TPU): the
    router's products ask for the highest precision themselves."""
    cfg = replace(reduced_config(NAME), router_score=score)
    # a softmax router takes its product in the compute dtype
    dt = jnp.float32 if score == "sigmoid" else jnp.bfloat16
    rng = np.random.default_rng(5)
    xt = jnp.asarray(rng.normal(size=(64, cfg.d_model)), dt)
    router = jnp.asarray(rng.normal(size=(cfg.d_model, cfg.n_experts))
                         * 0.02, dt)
    with jax.default_matmul_precision("bfloat16"):
        jaxpr = jax.make_jaxpr(L._route, static_argnums=2)(xt, router, cfg)
        scores, gate, gidx = jax.jit(L._route, static_argnums=2)(
            xt, router, cfg)
    f32_dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
                and all(v.aval.dtype == jnp.float32 for v in e.invars)]
    assert f32_dots and all(
        e.params["precision"] == (jax.lax.Precision.HIGHEST,) * 2
        for e in f32_dots)
    top, idx = jax.lax.top_k(scores, cfg.experts_per_token)
    np.testing.assert_array_equal(np.asarray(gidx), np.asarray(idx))
    want = cfg.routed_scaling * top / top.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(gate), np.asarray(want),
                               rtol=1e-6)


def test_dropless_under_a_planted_imbalance():
    """Every row routed to one held expert: the dropless layer computes
    all of them, as the reference does; the capacity-bound layer
    cannot."""
    cfg = reduced_config(NAME)
    model = tiny_model()
    p = _layer(cfg, model, SEED)
    rng = np.random.default_rng(3)
    h = rng.normal(size=(2, 32, cfg.d_model)).astype(np.float32)
    h[..., 0] = np.abs(h[..., 0]) + 4.0           # a common direction...
    router = np.asarray(p["ffn"]["router"]).copy()
    router[0, cfg.expert_offset + 1] = 50.0       # ...that expert 1 scores
    p["ffn"]["router"] = jnp.asarray(router)
    h = jnp.asarray(h)
    kind = cfg.block_pattern()[1]
    got, _, aux = M._apply_layer(h, p, cfg, kind, None, None)
    want, counts = _ref_layer(h, p, model)
    assert counts[1] == 64                        # every row of the batch
    assert int(aux["moe_max_rows"]) == 64
    assert int(aux["moe_rows"]) == int(counts.sum())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    capped, _, _ = L.moe_ffn(
        L.rms_norm(h, p["ln1"]["w"], cfg.norm_eps), p["ffn"],
        replace(cfg, capacity_factor=1.0))
    assert not np.allclose(np.asarray(h + capped), np.asarray(want),
                           atol=1e-3)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The parts that 16 disjoint held sets give, with the shared expert
    counted once, add up to the reference's layer over all experts."""
    E, held = 32, 2
    cfg = replace(reduced_config(NAME), n_experts=E, experts_held=held)
    model = dict(tiny_model(), n_routed_experts_published=E,
                 n_routed_experts=E, expert_offset=0)
    p = _layer(cfg, model, SEED)                  # all 32 experts' weights
    h = jnp.asarray(np.random.default_rng(4).normal(
        size=(2, 32, cfg.d_model)), jnp.float32)
    want, _ = _ref_layer(h, p, model)
    u = L.rms_norm(h, p["ln1"]["w"], cfg.norm_eps)
    shared = L.mlp(u, p["ffn"]["shared"], cfg)
    total = h + shared
    for off in range(0, E, held):
        part = dict(p["ffn"], experts=jax.tree_util.tree_map(
            lambda w: w[off:off + held], p["ffn"]["experts"]))
        y, _, _ = L.moe_held_ffn(u, part, replace(cfg, expert_offset=off))
        total = total + (y - shared)
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


# sha256 of the mamba2 preset's ssm_mixer output on the inputs below,
# recorded from the tree before the Nemotron-H layers were added
MIXER_DIGESTS = {
    "float32":
        "a3cd369e2b3a3e1e677694c3118c6a734b211936b331cc53f2420fe9c94d7086",
    "bfloat16":
        "2da4962b2badce1aeec97e1152d5df905da3ce3e4ee7baddc45b86f16509d79a",
}


@pytest.mark.parametrize("dtype", sorted(MIXER_DIGESTS))
def test_mamba2_mixer_unchanged_bit_for_bit(dtype):
    cfg = replace(get_config("mamba2-130m"), d_model=64, ssm_state_dim=16,
                  ssm_head_dim=16, ssm_chunk=16, compute_dtype=dtype)
    rng = np.random.default_rng(20261018)
    shapes = L.ssm_params_shapes(cfg)
    p = {k: jnp.asarray(rng.normal(size=s) * 0.1, jnp.float32)
         for k, s in shapes.items()}
    x = jnp.asarray(rng.normal(size=(2, 32, 64)), dtype)
    y, _ = jax.jit(lambda x, p: L.ssm_mixer(x, p, cfg))(x, p)
    digest = hashlib.sha256(np.asarray(y).tobytes()).hexdigest()
    assert digest == MIXER_DIGESTS[dtype]


def test_gmm_kernel_matches_ragged_dot(monkeypatch):
    """The Pallas grouped product (interpreted) and ``lax.ragged_dot``
    agree on the routed rows, and so do their gradients."""
    rng = np.random.default_rng(5)
    lhs = jnp.asarray(rng.normal(size=(256, 64)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 64, 48)), jnp.float32)
    sizes = jnp.asarray([70, 0, 100, 30], jnp.int32)
    n = int(sizes.sum())

    def run():
        f = lambda a, b: L._gmm(a, b, sizes)[:n]      # noqa: E731
        y = f(lhs, rhs)
        g = jax.grad(lambda a, b: jnp.sum(f(a, b) ** 2), (0, 1))(lhs, rhs)
        return y, g

    monkeypatch.delenv("REPRO_USE_PALLAS", raising=False)
    y0, (ga0, gb0) = run()
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    y1, (ga1, gb1) = run()
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0), rtol=1e-5,
                               atol=1e-4)
    np.testing.assert_allclose(np.asarray(ga1[:n]), np.asarray(ga0[:n]),
                               rtol=1e-5, atol=1e-3)
    np.testing.assert_allclose(np.asarray(gb1), np.asarray(gb0), rtol=1e-5,
                               atol=1e-3)


@pytest.mark.parametrize("d,tile", [(2688, 896), (1856, 640), (48, 48),
                                    (128, 128)])
def test_gmm_tiles_pad_little(d, tile):
    assert L._gmm_tile(d) == tile


def test_trainer_that_donates_saves_and_restores(monkeypatch):
    """A trainer that donates its state fetches it at a save, before the
    next step deletes it; the checkpoint restores bit-equal."""
    from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                                  ObjectStore, ReplicatedStore)
    from repro.core import Log, LogConfig, PMEMDevice
    from repro.data import DataConfig, SyntheticDataset
    from repro.optim import OptConfig
    from repro.train import trainer as T
    monkeypatch.setattr(T, "_donates", lambda cfg, opt_cfg: True)
    cfg = reduced_config(NAME)
    data = SyntheticDataset(cfg, DataConfig(batch=2, seq_len=32))
    store = ReplicatedStore([ObjectStore(f"s{i}") for i in range(2)],
                            write_quorum=1)
    log = Log.create(PMEMDevice((1 << 18) + 4096),
                     LogConfig(capacity=1 << 18))
    mgr = CheckpointManager(store, log, CheckpointConfig(force_freq=1))
    opt = OptConfig(name="adamw", lr=3e-3, warmup_steps=2,
                    decay_steps=1000)
    tr = T.Trainer(cfg, opt, data, mgr,
                   T.TrainerConfig(total_steps=4, ckpt_every=2))
    assert tr.donates
    tr.init_or_restore()
    tr.run(n_steps=2)           # a run drains its save before it returns,
    rep = tr.run(n_steps=2)     # so neither save can be skipped
    final = jax.device_get(tr.state)
    assert rep.ckpts_saved == 2 and rep.ckpts_skipped == 0
    assert rep.moe_rows > 0 and rep.moe_max_rows > 0
    step, state, _ = mgr.restore(final)
    assert step == 4
    for a, b in zip(jax.tree_util.tree_leaves(final),
                    jax.tree_util.tree_leaves(state)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
    mgr.close()


def test_small_states_are_not_donated():
    from repro.optim import OptConfig
    from repro.train.trainer import _donates
    cfg = reduced_config(NAME)
    # no memory limit reported (the CPU): nothing is donated
    assert not _donates(cfg, OptConfig())


def _garbage_gmm():
    """``lax.ragged_dot`` with what the TPU kernel leaves undefined made
    NaN: output rows past the routed ones, and the same rows of the
    input's gradient."""
    @jax.custom_vjp
    def gmm(lhs, rhs, sizes):
        out = jax.lax.ragged_dot(lhs, rhs, sizes,
                                 preferred_element_type=lhs.dtype)
        past = jnp.arange(lhs.shape[0])[:, None] >= sizes.sum()
        return jnp.where(past, jnp.nan, out)

    def fwd(lhs, rhs, sizes):
        return gmm(lhs, rhs, sizes), (lhs, rhs, sizes)

    def bwd(res, g):
        lhs, rhs, sizes = res
        _, vjp = jax.vjp(lambda a, b: jax.lax.ragged_dot(
            a, b, sizes, preferred_element_type=a.dtype), lhs, rhs)
        ga, gb = vjp(jnp.nan_to_num(g))
        past = jnp.arange(lhs.shape[0])[:, None] >= sizes.sum()
        return jnp.where(past, jnp.nan, ga), gb, None

    gmm.defvjp(fwd, bwd)
    return gmm


def test_rows_the_kernel_leaves_undefined_stay_out(monkeypatch):
    """NaN in the rows past the routed ones, in both passes, changes
    neither the expert layer's output nor any gradient."""
    cfg = reduced_config(NAME)
    model = tiny_model()
    p = _layer(cfg, model, SEED)
    h = jnp.asarray(np.random.default_rng(6).normal(
        size=(2, 32, cfg.d_model)), jnp.float32)
    kind = cfg.block_pattern()[1]

    def run():
        f = lambda h, p: M._apply_layer(h, p, cfg, kind, None, None)[0]  # noqa: E731
        y = f(h, p)
        g = jax.grad(lambda h, p: jnp.sum(f(h, p) ** 2), (0, 1))(h, p)
        return y, g

    y0, g0 = run()
    monkeypatch.setattr(L, "_gmm", _garbage_gmm())
    y1, g1 = run()
    np.testing.assert_array_equal(np.asarray(y1), np.asarray(y0))
    for a, b in zip(jax.tree_util.tree_leaves(g1),
                    jax.tree_util.tree_leaves(g0)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6,
                                   atol=1e-7)
