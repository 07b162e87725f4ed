"""Per-kernel validation: shape/dtype sweeps, Pallas (interpret mode)
vs pure-jnp oracle.  Checksum is an integer hash => exact equality;
float kernels use assert_allclose with dtype-appropriate tolerances."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.checksum.checksum import tensor_checksum_pallas
from repro.kernels.checksum.ref import tensor_checksum
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_reference
from repro.kernels.ssd_scan.ref import (ssd_reference,
                                        ssd_sequential_oracle)
from repro.kernels.ssd_scan.ssd_scan import ssd_pallas


# ------------------------------ checksum ------------------------------- #

@pytest.mark.parametrize("shape", [(128,), (1000,), (256, 128), (7, 33, 5),
                                   (2, 3, 4, 5)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8", "int32"])
def test_checksum_matches_ref(shape, dtype):
    rng = np.random.default_rng(hash((shape, dtype)) % 2**32)
    x = jnp.asarray(rng.normal(size=shape) * 10).astype(dtype)
    assert int(tensor_checksum(x)) == \
        int(tensor_checksum_pallas(x, interpret=True))


def test_checksum_detects_single_bit_flip():
    rng = np.random.default_rng(0)
    x = rng.normal(size=4096).astype(np.float32)
    base = int(tensor_checksum(jnp.asarray(x)))
    for byte in [0, 999, len(x.tobytes()) - 1]:
        raw = bytearray(x.tobytes())
        raw[byte] ^= 0x10
        y = np.frombuffer(bytes(raw), np.float32)
        assert int(tensor_checksum(jnp.asarray(y))) != base


def test_checksum_detects_torn_8byte_unit():
    """The exact failure mode of the PMEM model: an 8-byte unit reverts."""
    rng = np.random.default_rng(1)
    x = rng.normal(size=2048).astype(np.float32)
    base = int(tensor_checksum(jnp.asarray(x)))
    raw = bytearray(x.tobytes())
    raw[512:520] = b"\0" * 8
    y = np.frombuffer(bytes(raw), np.float32)
    assert int(tensor_checksum(jnp.asarray(y))) != base


@pytest.mark.parametrize("lanes", [1, 7, 259, 4096, 5000])
def test_checksum_batch_matches_per_row(lanes):
    """The batched validator (recovery scan) must be integer-identical to
    the per-tensor hash, including rows padded past their logical end
    (trailing zero lanes contribute nothing to the polynomial)."""
    from repro.kernels.checksum.ops import tensor_checksum_batch
    from repro.kernels.checksum.ref import checksum_lanes_2d
    rng = np.random.default_rng(lanes)
    mat = rng.integers(0, 2 ** 32, size=(5, lanes), dtype=np.uint32)
    mat[2, lanes // 2:] = 0                  # a zero-padded row
    batch = np.asarray(tensor_checksum_batch(mat), np.uint32)
    oracle = np.asarray(checksum_lanes_2d(jnp.asarray(mat)), np.uint32)
    per_row = np.array([int(tensor_checksum(jnp.asarray(r))) for r in mat],
                       np.uint32)
    np.testing.assert_array_equal(batch, per_row)
    np.testing.assert_array_equal(oracle, per_row)
    # pallas route agrees too (interpret mode off-TPU)
    pallas = np.asarray(tensor_checksum_batch(mat, use_pallas=True),
                        np.uint32)
    np.testing.assert_array_equal(pallas, per_row)


@pytest.mark.parametrize("lanes", [2 * 32768, 3 * 32768 + 5, 5 * 32768 - 1])
def test_checksum_kernel_spans_blocks(lanes):
    """Rows of several 128 KiB kernel blocks: the int32 kernel with its
    lane-dense partials still equals the oracle and the NumPy batch
    path bit for bit (stored hash values must never change)."""
    from repro.kernels.checksum.checksum import BLOCK, hash_rows_pallas
    from repro.kernels.checksum.ops import tensor_checksum_batch
    assert lanes > BLOCK
    rng = np.random.default_rng(lanes)
    mat = rng.integers(0, 2 ** 32, size=(3, lanes), dtype=np.uint32)
    mat[1, lanes // 3:] = 0
    host = np.asarray(tensor_checksum_batch(mat, use_pallas=False),
                      np.uint32)
    kernel_rows = hash_rows_pallas(mat, interpret=True)
    per_row = [int(tensor_checksum_pallas(jnp.asarray(r), interpret=True))
               for r in mat]
    oracle = [int(tensor_checksum(jnp.asarray(r))) for r in mat]
    np.testing.assert_array_equal(kernel_rows, host)
    assert per_row == oracle == host.tolist()


# --------------------------- flash attention --------------------------- #

@pytest.mark.parametrize("B,H,KV,S,D", [
    (2, 4, 2, 256, 64), (1, 8, 8, 128, 128), (2, 2, 1, 512, 32),
    (1, 4, 2, 384, 64),
])
def test_flash_attention_causal(B, H, KV, S, D):
    rng = np.random.default_rng(B * S)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, KV, S, D)), jnp.float32)
    ref = attention_reference(q, k, v, causal=True)
    out = flash_attention_pallas(q, k, v, causal=True, bq=128, bk=128,
                                 interpret=True)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kw", [
    dict(causal=False),
    dict(causal=True, window=128),
    dict(causal=True, cap=50.0),
    dict(causal=True, window=64, cap=30.0),
])
def test_flash_attention_mask_variants(kw):
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    ref = attention_reference(q, k, v, **kw)
    out = flash_attention_pallas(q, k, v, bq=128, bk=128, interpret=True,
                                 **kw)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_flash_attention_bf16():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.bfloat16)
    ref = attention_reference(q, k, v, causal=True).astype(jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, bq=128, bk=128,
                                 interpret=True).astype(jnp.float32)
    np.testing.assert_allclose(out, ref, atol=3e-2, rtol=3e-2)


# ------------------------------ SSD scan ------------------------------- #

@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 64, 4, 32, 2, 16, 16),
    (1, 128, 2, 64, 1, 32, 32),
    (1, 96, 6, 16, 3, 8, 16),       # chunk does not divide heads evenly
])
def test_ssd_chunked_matches_sequential(B, S, H, P, G, N, chunk):
    rng = np.random.default_rng(S + H)
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.9, size=(B, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1.0, 0.5, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    y_seq, st_seq = ssd_sequential_oracle(xh, dt, A_log, Bm, Cm)
    y_ref, st_ref = ssd_reference(xh, dt, A_log, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(y_ref, y_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st_ref, st_seq, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 64, 4, 32, 2, 16, 16),
    (1, 128, 2, 64, 1, 32, 32),
    (2, 64, 4, 32, 4, 16, 64),      # G == H (no grouping)
])
def test_ssd_pallas_matches_sequential(B, S, H, P, G, N, chunk):
    rng = np.random.default_rng(S * H)
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.05, 0.9, size=(B, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1.0, 0.5, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    Cm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32)
    y_seq, st_seq = ssd_sequential_oracle(xh, dt, A_log, Bm, Cm)
    y_k, st_k = ssd_pallas(xh, dt, A_log, Bm, Cm, chunk=chunk,
                           interpret=True)
    np.testing.assert_allclose(y_k, y_seq, atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(st_k, st_seq, atol=1e-4, rtol=1e-4)


def test_ssd_bf16_inputs():
    rng = np.random.default_rng(5)
    B, S, H, P, G, N = 1, 64, 2, 32, 1, 16
    xh = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.bfloat16)
    dt = jnp.asarray(rng.uniform(0.05, 0.9, size=(B, S, H)), jnp.float32)
    A_log = jnp.asarray(rng.uniform(-1.0, 0.5, size=(H,)), jnp.float32)
    Bm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.bfloat16)
    Cm = jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.bfloat16)
    y_ref, st_ref = ssd_reference(xh, dt, A_log, Bm, Cm, chunk=16)
    y_k, st_k = ssd_pallas(xh, dt, A_log, Bm, Cm, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_k, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=5e-2, rtol=5e-2)


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", [
    (2, 64, 4, 32, 2, 16, 16),
    (1, 128, 2, 64, 1, 32, 32),
])
def test_ssd_pallas_grad_matches_reference(B, S, H, P, G, N, chunk):
    """ssd_pallas's custom VJP == jax.grad through ssd_reference."""
    rng = np.random.default_rng(B * S + H)
    args = (jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32),
            jnp.asarray(rng.uniform(0.05, 0.9, size=(B, S, H)), jnp.float32),
            jnp.asarray(rng.uniform(-1.0, 0.5, size=(H,)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32))
    wy = jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32)
    ws = jnp.asarray(rng.normal(size=(B, H, P, N)), jnp.float32)

    def loss(fn):
        def f(*a):
            y, st = fn(*a)
            return jnp.sum(y * wy) + jnp.sum(st * ws)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))

    g_k = loss(lambda *a: ssd_pallas(*a, chunk=chunk, interpret=True))(*args)
    g_r = loss(lambda *a: ssd_reference(*a, chunk=chunk))(*args)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_ssd_grad_finite_over_long_chunks():
    """Strong decays over a 256-token chunk push A_i - A_j far past the
    float32 exp range above the diagonal; the masked terms must not turn
    the gradient into NaN (kernel VJP and reference alike)."""
    rng = np.random.default_rng(11)
    B, S, H, P, G, N, chunk = 1, 256, 2, 16, 1, 16, 256
    args = (jnp.asarray(rng.normal(size=(B, S, H, P)), jnp.float32),
            jnp.asarray(rng.uniform(1.0, 2.0, size=(B, S, H)), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 0.5, size=(H,)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32),
            jnp.asarray(rng.normal(size=(B, S, G, N)), jnp.float32))

    def grads(fn):
        def f(*a):
            y, st = fn(*a)
            return jnp.sum(y ** 2) + jnp.sum(st)
        return jax.grad(f, argnums=(0, 1, 2, 3, 4))(*args)

    for fn in (lambda *a: ssd_reference(*a, chunk=chunk),
               lambda *a: ssd_pallas(*a, chunk=chunk, interpret=True)):
        for g in grads(fn):
            assert np.all(np.isfinite(np.asarray(g)))
