"""Gradient compression: int8 ring all-reduce (quantize → all_to_all →
local int32 accumulate → requantize → all_gather).

A plain ``psum`` moves fp32 on the wire; this moves int8 chunks plus one
tiny fp32 scale exchange — ~4× fewer DCN bytes for cross-pod gradient
reduction.  Quantization is symmetric per-shard-max with optional
stochastic rounding (unbiased in expectation).

Runs inside shard_map over the reduction axis.  ``compressed_psum`` is
the drop-in for ``lax.psum`` on gradient pytree leaves.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def _quantize(x, scale, key=None):
    y = x / jnp.maximum(scale, 1e-30)
    if key is not None:
        y = jnp.floor(y + jax.random.uniform(key, y.shape))
    else:
        y = jnp.round(y)
    return jnp.clip(y, -127, 127).astype(jnp.int8)


def compressed_psum(x: jax.Array, axis: str,
                    key: Optional[jax.Array] = None) -> jax.Array:
    """int8 ring all-reduce of ``x`` over mesh axis ``axis``.
    Call inside shard_map.  x's leading dim must be divisible by the
    axis size (pad upstream)."""
    n = lax.psum(1, axis)
    orig_shape = x.shape
    flat = x.reshape(-1)
    chunk = flat.shape[0] // n
    xs = flat.reshape(n, chunk)                     # my contribution, split
    # global symmetric scale (one tiny fp32 all-reduce)
    scale = lax.pmax(jnp.max(jnp.abs(flat)), axis) / 127.0
    q = _quantize(xs, scale, key)                   # [n, chunk] int8
    # reduce-scatter phase: chunk j of every rank lands on rank j
    recv = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                          tiled=False)              # [n, chunk] int8
    acc = jnp.sum(recv.astype(jnp.int32), axis=0)   # local accumulate
    # requantize the partial sum and all-gather int8 (scale grows by n)
    scale2 = scale * n
    q2 = jnp.clip(jnp.round(acc.astype(jnp.float32) * scale /
                            jnp.maximum(scale2, 1e-30)),
                  -127, 127).astype(jnp.int8)
    gathered = lax.all_gather(q2, axis, axis=0)     # [n, chunk] int8
    out = gathered.astype(jnp.float32) * scale2
    return out.reshape(orig_shape).astype(x.dtype)


def quantized_allreduce(x: jax.Array, mesh: Mesh, axis: str,
                        key: Optional[jax.Array] = None) -> jax.Array:
    """Convenience wrapper: shard_map'd compressed_psum for a tensor
    replicated over ``axis`` (e.g. per-pod gradient replicas)."""
    fn = jax.shard_map(partial(compressed_psum, axis=axis, key=key),
                       mesh=mesh, in_specs=P(axis), out_specs=P(axis),
                       check_vma=False)
    return fn(x)
