"""Dispatch for the tensor integrity hash: Pallas on TPU, jnp ref
elsewhere (identical results by construction — tests assert equality,
not allclose: it's an integer hash)."""

from __future__ import annotations

import os

import jax

from ... import obs
from .checksum import hash_rows_pallas, tensor_checksum_pallas
from .ref import tensor_checksum as tensor_checksum_ref
from .ref import tree_checksums as tree_checksums_ref


def _want_pallas(use_pallas) -> bool:
    if use_pallas is not None:
        return use_pallas
    if os.environ.get("REPRO_USE_PALLAS") == "1":
        return True
    return jax.default_backend() == "tpu"


def tensor_checksum(x, use_pallas=None):
    if _want_pallas(use_pallas):
        return tensor_checksum_pallas(
            x, interpret=jax.default_backend() != "tpu")
    return tensor_checksum_ref(x)


def tensor_checksum_batch(mat, use_pallas=None):
    """Batched integrity hash: uint32 lane matrix [n, L] -> uint32[n].

    Rows are zero-padded to the common lane count L — trailing zero lanes
    contribute nothing to the polynomial, so each row's value equals
    tensor_checksum of its unpadded bytes.  The recovery scan validates
    every FLAG_PHASH payload in one call here instead of one kernel
    dispatch per record.

    Off-TPU the blockwise evaluation runs directly in NumPy on the host
    (uint32 multiply-add wraps mod 2^32, integer-identical to the jnp
    oracle and the Pallas kernel — tests assert ==); on TPU the whole
    matrix goes through the Pallas kernel in one call.
    """
    import numpy as np

    mat = np.ascontiguousarray(mat, dtype=np.uint32)
    if mat.ndim != 2:
        raise ValueError(f"expected a [rows, lanes] matrix, got {mat.shape}")
    rows, n = mat.shape
    if rows == 0 or n == 0:
        return np.zeros((rows,), np.uint32)
    with obs.span(obs.CHECKSUM_CALL):
        on_tpu = jax.default_backend() == "tpu"
        if use_pallas or (use_pallas is None and on_tpu):
            return hash_rows_pallas(mat, interpret=not on_tpu)
        return _hash_rows_numpy(mat)


def _hash_rows_numpy(mat):
    """The blockwise evaluation of ``tensor_checksum_batch`` in NumPy."""
    import numpy as np

    from .ref import _BLOCK, _R_BLOCK, powers
    rows, n = mat.shape
    if n <= _BLOCK:
        return (mat * powers(n)[None, :]).sum(axis=1, dtype=np.uint32)
    pad = (-n) % _BLOCK
    if pad:
        mat = np.concatenate(
            [mat, np.zeros((rows, pad), np.uint32)], axis=1)
    nb = mat.shape[1] // _BLOCK
    blocks = mat.reshape(rows, nb, _BLOCK)
    partials = (blocks * powers(_BLOCK)[None, None, :]).sum(
        axis=2, dtype=np.uint32)
    facs = np.empty(nb, np.uint32)
    acc = np.uint32(1)
    for b in range(nb):
        facs[b] = acc
        acc = np.uint32((int(acc) * int(_R_BLOCK)) & 0xFFFFFFFF)
    return (partials * facs[None, :]).sum(axis=1, dtype=np.uint32)


def tree_checksums(tree, use_pallas=None):
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.stack([tensor_checksum(l, use_pallas) for l in leaves])
