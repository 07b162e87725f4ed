"""GPipe-style pipeline parallelism over a mesh axis (shard_map +
collective_permute).

Stages hold disjoint layer slices (leading ``n_stages`` dim of the stage
params, sharded over the pipeline axis).  Microbatches stream through:
at tick t, stage i processes microbatch t-i; activations hop stages via
``lax.ppermute``.  Bubble fraction = (S-1)/(M+S-1) — the launcher picks
M >= 4·S by default.

This is a config option for the pod axis (multi-pod meshes): DP across
pods is the default; ``--pipeline-pods`` turns the pod axis into a
pipeline axis instead (cross-pod DCN traffic becomes activation hops
instead of gradient all-reduces).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _pipeline_local(stage_fn: Callable, params_local, x_local, *,
                    axis: str, n_micro: int):
    """Runs inside shard_map: params_local has leading dim 1 (this
    stage's slice); x_local [n_micro, mb, ...] replicated."""
    n = lax.psum(1, axis)
    i = lax.axis_index(axis)
    p_local = jax.tree_util.tree_map(lambda a: a[0], params_local)
    state = jnp.zeros_like(x_local[0])
    out = jnp.zeros_like(x_local)
    perm = [(s, (s + 1) % n) for s in range(n)]
    T = n_micro + n - 1
    for t in range(T):                       # static schedule
        feed = x_local[min(t, n_micro - 1)]
        inp = jnp.where(i == 0, feed, state)
        y = stage_fn(p_local, inp)
        state = lax.ppermute(y, axis, perm)
        emit = t - (n - 1)
        if emit >= 0:
            upd = out.at[emit].set(y)
            out = jnp.where(i == n - 1, upd, out)
    # broadcast the last stage's outputs to every stage
    return lax.psum(jnp.where(i == n - 1, out, jnp.zeros_like(out)), axis)


def pipeline_forward(stage_fn: Callable, stage_params, x, *, mesh: Mesh,
                     axis: str, n_micro: int):
    """stage_params: pytree with leading dim n_stages on every leaf
    (sharded over ``axis``); x [n_micro, mb, ...] (replicated over
    ``axis``).  Returns y [n_micro, mb, ...] replicated over ``axis``."""
    pspec = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        partial(_pipeline_local, stage_fn, axis=axis, n_micro=n_micro),
        mesh=mesh,
        in_specs=(pspec, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
