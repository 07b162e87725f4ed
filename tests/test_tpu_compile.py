"""The main-path kernels compile for a TPU v5e that is described, not
attached: the checksum kernel at 1 MiB and 4 MiB inputs, the SSD kernel
forward and ``jax.grad`` at mamba2-130m widths, flash attention at
8 heads of 128 over 2048 tokens, the XLA blockwise attention's softmax,
and the Nemotron-H stage's attention layer through the splash kernel.
Nothing runs;
this catches what the chip's compiler would refuse, at no chip time.

The topology is described inside a module fixture (never at import):
only one process at a time may load the TPU library."""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.checksum.checksum import (BLOCK, _hash_rows,
                                             tensor_checksum_pallas)
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.ssd_scan.ssd_scan import ssd_pallas

# mamba2-130m: 24 heads of 64, state 128, chunk 256, one group
B, S, H, P, G, N, Q = 1, 2048, 24, 64, 1, 128, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _ssd_args(one_chip):
    return (_spec((B, S, H, P), jnp.bfloat16, one_chip),
            _spec((B, S, H), jnp.float32, one_chip),
            _spec((H,), jnp.float32, one_chip),
            _spec((B, S, G, N), jnp.bfloat16, one_chip),
            _spec((B, S, G, N), jnp.bfloat16, one_chip))


@pytest.mark.parametrize("nbytes", [1 << 20, 4 << 20])
def test_checksum_kernel_compiles(one_chip, nbytes):
    compiled = jax.jit(tensor_checksum_pallas).lower(
        _spec((nbytes,), jnp.uint8, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("blocks", [8, 32])
def test_checksum_batch_kernel_compiles(one_chip, blocks):
    """The recovery scan's one batched call: rows × blocks."""
    compiled = _hash_rows.lower(
        _spec((16, blocks * BLOCK), jnp.uint32, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_forward_compiles(one_chip):
    compiled = jax.jit(lambda *a: ssd_pallas(*a, chunk=Q)).lower(
        *_ssd_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_attention_kernel_compiles(one_chip):
    q = _spec((1, 8, 2048, 128), jnp.bfloat16, one_chip)
    compiled = jax.jit(flash_attention_pallas).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_kernel_grad_compiles(one_chip):
    def loss(xh, dt, A_log, Bm, Cm):
        y, state = ssd_pallas(xh, dt, A_log, Bm, Cm, chunk=Q)
        return jnp.sum(y.astype(jnp.float32) ** 2) + jnp.sum(state)

    grad = jax.grad(loss, argnums=(0, 1, 2, 3, 4))
    compiled = jax.jit(grad).lower(*_ssd_args(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_blockwise_attention_softmax_is_no_row_wide_window(one_chip):
    """Forward and ``jax.grad`` of the XLA blockwise attention at the
    Nemotron-H stage's shape (2 x 8192 tokens, 2 KV heads of 16 queries
    of 128, 128-query blocks; smaller shapes do not show the rewrite):
    no reduce-window spans the key axis, which would cost the key
    length at every position."""
    import re
    from repro.models.layers import attention
    Sa = 8192
    q = _spec((2, Sa, 2, 16, 128), jnp.bfloat16, one_chip)
    kv = _spec((2, Sa, 2, 128), jnp.bfloat16, one_chip)

    def f(q, k, v):
        o = attention(q, k, v, causal=True, chunk_q=128)
        return jnp.sum(jnp.square(o.astype(jnp.float32)))

    txt = jax.jit(jax.grad(f, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile().as_text()
    widths = [int(w) for w in
              re.findall(r"window=\{size=(?:\d+x)*(\d+)", txt)]
    assert max(widths, default=1) < Sa, sorted(set(widths))


def test_nemotron_attention_layer_compiles_through_flash(one_chip,
                                                          monkeypatch):
    """``gqa_attention`` at the train8k cell's widths (d_model 2688, 32
    query heads over 2 KV heads of 128, 2 x 8192 tokens), forward and
    ``jax.grad``: on the TPU it runs the splash kernel, with no
    reduce-window wider than 128 and no more temporary memory than the
    XLA blockwise path compiled at the same shape."""
    import re
    from repro.configs import get_config
    from repro.models import layers
    cfg = get_config("nemotron3-nano-30b-a3b")
    x = _spec((2, 8192, cfg.d_model), jnp.bfloat16, one_chip)
    p = {n: _spec(s, jnp.bfloat16, one_chip)
         for n, s in layers.gqa_params_shapes(cfg).items()}

    def loss(x, p):
        y, _ = layers.gqa_attention(x, p, cfg, local=False)
        return jnp.sum(jnp.square(y.astype(jnp.float32)))

    def compile_grad():
        return jax.jit(jax.grad(loss, argnums=(0, 1))).lower(x, p).compile()

    xla = compile_grad()                   # the CPU backend: blockwise
    assert "tpu_custom_call" not in xla.as_text()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    flash = compile_grad()
    txt = flash.as_text()
    assert "tpu_custom_call" in txt
    widths = [int(w) for w in
              re.findall(r"window=\{size=(?:\d+x)*(\d+)", txt)]
    assert max(widths, default=1) <= 128, sorted(set(widths))
    assert flash.memory_analysis().temp_size_in_bytes <= \
        xla.memory_analysis().temp_size_in_bytes
