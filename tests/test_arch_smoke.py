"""Per-architecture smoke tests: reduced config, one forward/train step
on CPU, shape + finiteness assertions; prefill→decode consistency for
causal archs."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config, reduced_config
from repro.models import model as M
from repro.models.config import applicable_shapes

SEQ = 32
BATCH = 2


def make_batch(cfg, rng, seq=SEQ, batch=BATCH, labels=True):
    out = {}
    if cfg.input_kind == "frames":
        out["frames"] = jnp.asarray(
            rng.normal(size=(batch, seq, cfg.frontend_dim)), jnp.float32)
    elif cfg.input_kind == "tokens+patches":
        npatch = cfg.n_patches
        out["patches"] = jnp.asarray(
            rng.normal(size=(batch, npatch, cfg.frontend_dim)), jnp.float32)
        out["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq - npatch)), jnp.int32)
    else:
        out["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32)
    if labels:
        lab = rng.integers(0, cfg.vocab_size, (batch, seq))
        if cfg.input_kind == "tokens+patches":
            lab[:, :cfg.n_patches] = -1       # no loss on patch positions
        out["labels"] = jnp.asarray(lab, jnp.int32)
    return out


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_forward_and_grad_step(name):
    cfg = reduced_config(name)
    rng = np.random.default_rng(0)
    params = M.init_params(jax.random.key(0), cfg)
    batch = make_batch(cfg, rng)

    def loss_fn(p):
        loss, metrics = M.forward_train(p, cfg, batch)
        return loss, metrics

    (loss, metrics), grads = jax.jit(
        jax.value_and_grad(loss_fn, has_aux=True))(params)
    assert np.isfinite(float(loss)), f"{name}: loss not finite"
    assert float(loss) > 0
    gnorm = jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                         for g in jax.tree_util.tree_leaves(grads)))
    assert np.isfinite(float(gnorm)) and float(gnorm) > 0, \
        f"{name}: bad grad norm"


@pytest.mark.parametrize("name", [n for n in ARCH_NAMES
                                  if get_config(n).causal])
def test_prefill_decode_consistency(name):
    """Teacher-forced decode must reproduce the prefill logits."""
    cfg = reduced_config(name)
    rng = np.random.default_rng(1)
    params = M.init_params(jax.random.key(1), cfg)
    seq = SEQ
    batch = make_batch(cfg, rng, seq=seq, labels=False)

    # full-sequence forward (no cache) as the reference
    ref_logits, _ = jax.jit(
        lambda p, b: M.serve_step(p, cfg, b, None, None))(params, batch)

    # prefill first half, then decode the second half token by token
    half = seq // 2
    cache = M.init_cache(cfg, BATCH, seq)
    if cfg.input_kind == "tokens+patches":
        npatch = cfg.n_patches
        pre = {"patches": batch["patches"],
               "tokens": batch["tokens"][:, : half - npatch]}
        tail = batch["tokens"][:, half - npatch:]
    else:
        pre = {"tokens": batch["tokens"][:, :half]}
        tail = batch["tokens"][:, half:]
    logits, cache = jax.jit(
        lambda p, b, c: M.serve_step(p, cfg, b, c, jnp.int32(0)))(
        params, pre, cache)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(ref_logits[:, :half]),
                               rtol=2e-4, atol=2e-4)

    decode = jax.jit(lambda p, t, c, i: M.serve_step(
        p, cfg, {"tokens": t}, c, i))
    for j in range(4):                      # a few steps is enough
        tok = tail[:, j : j + 1]
        logits_j, cache = decode(params, tok, cache, jnp.int32(half + j))
        np.testing.assert_allclose(
            np.asarray(logits_j[:, 0]), np.asarray(ref_logits[:, half + j]),
            rtol=2e-3, atol=2e-3,
            err_msg=f"{name}: decode step {j} diverges from prefill")


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_param_specs_match_init(name):
    cfg = reduced_config(name)
    specs = M.param_specs(cfg)
    params = M.init_params(jax.random.key(0), cfg)
    sflat, stree = jax.tree_util.tree_flatten(specs)
    pflat, ptree = jax.tree_util.tree_flatten(params)
    assert stree == ptree
    for s, p in zip(sflat, pflat):
        assert s.shape == p.shape and s.dtype == p.dtype


@pytest.mark.parametrize("name", ARCH_NAMES)
def test_full_config_param_count_sane(name):
    """Full (non-reduced) config param counts are in the right ballpark
    for the advertised sizes — catches mis-wired configs without
    allocating anything."""
    cfg = get_config(name)
    n = cfg.param_count()
    expected = {
        "hubert-xlarge": (0.7e9, 1.3e9),
        "moonshot-v1-16b-a3b": (14e9, 30e9),
        "deepseek-v3-671b": (600e9, 720e9),
        "mamba2-130m": (0.1e9, 0.17e9),
        "jamba-1.5-large-398b": (330e9, 420e9),
        "starcoder2-3b": (2.5e9, 3.6e9),
        "gemma2-9b": (8e9, 10.5e9),
        "command-r-35b": (30e9, 40e9),
        "qwen2-7b": (6.5e9, 8.5e9),
        "llava-next-34b": (30e9, 38e9),
        # one chip's share of one 7-layer stage (src/repro/configs)
        "nemotron3-nano-30b-a3b": (0.52e9, 0.54e9),
    }[name]
    assert expected[0] <= n <= expected[1], f"{name}: {n/1e9:.2f}B params"
    assert cfg.active_param_count() <= n
    assert applicable_shapes(cfg)
