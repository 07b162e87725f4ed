"""The flash path of ``models.layers.attention``: the bundled splash
kernel (interpret mode here) against the XLA paths it replaces, forward
and ``jax.grad``, and the rule that selects it."""

import math

import pytest

import jax
import jax.numpy as jnp

from repro.models import layers

# a small GQA causal shape: 2 KV heads of 2 queries each, head 128
B, S, K, G, D = 2, 512, 2, 2, 128
SCALE = 1.0 / math.sqrt(D)
# relative Frobenius gap of output and gradients: a few bfloat16 ulps
# (2^-8); both paths read about 0.4% against a float32 reference
TOL = 1e-2


def _inputs():
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (B, S, K, G, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, D), jnp.bfloat16)
    w = jax.random.normal(ks[3], (B, S, K, G, D), jnp.float32)
    return q, k, v, w


def _out_and_grads(f, q, k, v, w):
    def loss(q, k, v):
        return jnp.sum(f(q, k, v).astype(jnp.float32) * w)
    return [jax.jit(f)(q, k, v),
            *jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)]


def _flash(q, k, v):
    # two blocks a side: the block above the diagonal is skipped
    return layers._flash_attention(q, k, v, scale=SCALE, block=256,
                                   interpret=True)


XLA_PATHS = {
    "blockwise": lambda q, k, v: layers._blockwise_attention(
        q, k, v, scale=SCALE, causal=True, window=None, cap=None,
        q_offset=0, chunk_q=128, unroll=False),
    "direct": lambda q, k, v: layers._direct_attention(
        q, k, v, scale=SCALE, causal=True, window=None, cap=None,
        q_offset=0, kv_len=None),
}


@pytest.fixture(scope="module")
def flash_results():
    q, k, v, w = _inputs()
    return _out_and_grads(_flash, q, k, v, w)


@pytest.mark.parametrize("path", sorted(XLA_PATHS))
def test_flash_matches_xla_path(flash_results, path):
    q, k, v, w = _inputs()
    want = _out_and_grads(XLA_PATHS[path], q, k, v, w)
    for name, a, b in zip(("out", "dq", "dk", "dv"), flash_results, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gap = float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
        assert gap < TOL, (name, gap)


# -- the selection rule --------------------------------------------------- #
SEL = 4096            # long enough to leave the direct path


def _taken(monkeypatch, platform="tpu", sq=SEL, sk=SEL, **kw):
    """Whether ``attention`` runs the flash path, seen from shapes alone."""
    calls = []

    def flash(q, k, v, *, scale, block):
        calls.append(block)
        return jnp.zeros(q.shape[:-1] + v.shape[-1:], q.dtype)

    monkeypatch.setattr(layers, "_flash_attention", flash)
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    q = jax.ShapeDtypeStruct((1, sq, K, G, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, sk, K, D), jnp.bfloat16)
    jax.eval_shape(lambda q, k, v: layers.attention(q, k, v, chunk_q=128,
                                                    **kw), q, kv, kv)
    return calls


def test_flash_selected_for_long_global_causal_on_tpu(monkeypatch):
    assert _taken(monkeypatch) == [1024]
    assert _taken(monkeypatch, sq=SEL + 512, sk=SEL + 512) == [512]


@pytest.mark.parametrize("case", [
    dict(platform="cpu"),
    dict(causal=False),
    dict(window=1024),
    dict(cap=50.0),
    dict(kv_len=SEL),
    dict(q_offset=128),
    dict(sk=2 * SEL),
    dict(sq=SEL + 128, sk=SEL + 128),      # no block of 256 or more fits
], ids=lambda c: next(iter(c)) if len(c) == 1 else "indivisible")
def test_flash_not_selected(monkeypatch, case):
    assert _taken(monkeypatch, **case) == []
