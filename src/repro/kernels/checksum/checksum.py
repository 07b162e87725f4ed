"""Pallas TPU kernel: blockwise polynomial integrity hash.

Grid over row-blocks of the lane vector reshaped to (rows, 128): each
step loads a (BLOCK_ROWS, 128) tile into VMEM, multiplies by the
per-position weight tile (r^j for j inside the block) and folds the
product into one lane-dense (8, 128) tile of partial sums.  The wrapper
sums each block's tile and combines the blocks with r^(bL) factors —
the blockwise-combinable property from ref.py.

The TPU lowers no reduction over unsigned integers, so the kernel works
in int32: a wrapping multiply-add mod 2^32 gives the same bits as in
uint32, and the wrapper bitcasts on the way in and out.  Hash values are
unchanged (tests assert equality with ref.py).

This is the integrity primitive's hot spot on-device: hashing a
multi-GB checkpoint shard or state-delta at HBM bandwidth instead of
streaming it through the host CPU for CRC32.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .ref import R, as_lanes, powers

LANES = 128
SUBLANES = 8
BLOCK_ROWS = 256                      # 256×128 lanes = 128 KiB per tile
BLOCK = BLOCK_ROWS * LANES            # lanes per block


def _checksum_kernel(x_ref, w_ref, out_ref):
    prod = x_ref[...] * w_ref[...]    # [BLOCK_ROWS, LANES] int32, wraps
    out_ref[...] = jnp.sum(
        prod.reshape(BLOCK_ROWS // SUBLANES, SUBLANES, LANES), axis=0)


def checksum_blocks_pallas(lanes2d: jax.Array, interpret: bool = False
                           ) -> jax.Array:
    """lanes2d [rows, 128] uint32 (rows % BLOCK_ROWS == 0) ->
    per-block partial hashes [n_blocks] uint32."""
    rows = lanes2d.shape[0]
    assert rows % BLOCK_ROWS == 0 and lanes2d.shape[1] == LANES
    n_blocks = rows // BLOCK_ROWS
    x = jax.lax.bitcast_convert_type(lanes2d, jnp.int32)
    w = jnp.asarray(powers(BLOCK).view(np.int32).reshape(BLOCK_ROWS, LANES))
    parts = pl.pallas_call(
        _checksum_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda b: (b, 0)),
            pl.BlockSpec((BLOCK_ROWS, LANES), lambda b: (0, 0)),
        ],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * SUBLANES, LANES),
                                       jnp.int32),
        interpret=interpret,
    )(x, w)
    parts = jax.lax.bitcast_convert_type(parts, jnp.uint32)
    return jnp.sum(parts.reshape(n_blocks, SUBLANES * LANES), axis=1,
                   dtype=jnp.uint32)


@functools.lru_cache(maxsize=None)
def _block_factors(n_blocks: int) -> np.ndarray:
    """[r^(bL) for b < n_blocks] mod 2^32."""
    rL = pow(int(R), BLOCK, 1 << 32)
    out = np.empty(n_blocks, np.uint32)
    acc = 1
    for b in range(n_blocks):
        out[b] = acc
        acc = (acc * rL) & 0xFFFFFFFF
    return out


@functools.partial(jax.jit, static_argnames=("interpret",))
def _hash_rows(mat: jax.Array, interpret: bool) -> jax.Array:
    """[rows, nb*BLOCK] uint32 -> row hashes [rows] uint32."""
    rows, n = mat.shape
    nb = n // BLOCK
    parts = checksum_blocks_pallas(mat.reshape(-1, LANES),
                                   interpret=interpret).reshape(rows, nb)
    facs = jnp.asarray(_block_factors(nb))
    # combine: h = Σ_b part_b · r^(bL)
    return jnp.sum(parts * facs[None, :], axis=1, dtype=jnp.uint32)


def tensor_checksum_pallas(x: jax.Array, interpret: bool = False
                           ) -> jax.Array:
    """Full tensor hash via the kernel; matches ref.tensor_checksum."""
    lanes = as_lanes(x)
    pad = (-lanes.shape[0]) % BLOCK
    if pad or lanes.shape[0] == 0:
        lanes = jnp.pad(lanes, (0, pad or BLOCK))
    return _hash_rows(lanes.reshape(1, -1), interpret=interpret)[0]


def hash_rows_pallas(mat: np.ndarray, interpret: bool = False) -> np.ndarray:
    """Row hashes of a host uint32 lane matrix [rows, n] -> uint32[rows].

    Rows are zero-padded on the host to a power-of-two number of blocks
    (trailing zero lanes add nothing to the polynomial), so records of
    any size share a handful of compiled shapes, and the whole matrix
    goes to the device in one transfer and one kernel call.
    """
    rows, n = mat.shape
    nb = 1 << (max(1, -(-n // BLOCK)) - 1).bit_length()
    if nb * BLOCK != n:
        padded = np.zeros((rows, nb * BLOCK), np.uint32)
        padded[:, :n] = mat
        mat = padded
    return np.asarray(_hash_rows(jnp.asarray(mat), interpret=interpret))
