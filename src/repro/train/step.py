"""Training step builder.

``journal=True`` turns on the paper's technique inside the step: an
integrity summary (lane-parallel polynomial hash per updated leaf — the
integrity primitive, kernels/checksum) is computed on-device and
returned *replicated*, which under the multi-pod mesh lowers to a
cross-pod collective: the replication primitive's bytes are visible in
the compiled HLO and amortized by the frequency-based force policy (the
trainer invokes the journaled variant every F-th step only).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels.checksum import ops as cksum
from ..models import model as M
from ..models.config import ModelConfig
from ..optim import OptConfig, apply_updates, init_opt_state, \
    opt_state_specs


def train_state_specs(cfg: ModelConfig, opt_cfg: OptConfig):
    pspecs = M.param_specs(cfg)
    return {
        "params": pspecs,
        "opt": opt_state_specs(pspecs, opt_cfg),
        "step": jax.ShapeDtypeStruct((), jnp.int32),
    }


def init_train_state(rng, cfg: ModelConfig, opt_cfg: OptConfig):
    params = M.init_params(rng, cfg)
    return {
        "params": params,
        "opt": init_opt_state(params, opt_cfg),
        "step": jnp.zeros((), jnp.int32),
    }


def train_step(state, batch, *, cfg: ModelConfig, opt_cfg: OptConfig,
               journal: bool = False
               ) -> Tuple[Any, Dict[str, jax.Array]]:
    """One optimizer step.  Returns (new_state, metrics)."""
    def loss_fn(p):
        return M.forward_train(p, cfg, batch)

    (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
        state["params"])
    new_params, new_opt, opt_metrics = apply_updates(
        state["params"], grads, state["opt"], state["step"], opt_cfg)
    metrics = {**metrics, **opt_metrics}
    if journal:
        # integrity primitive over the state delta (per-leaf hash of the
        # gradients); replicated output => cross-pod replication in HLO
        metrics["integrity"] = cksum.tree_checksums(grads)
    new_state = {"params": new_params, "opt": new_opt,
                 "step": state["step"] + 1}
    return new_state, metrics


def make_train_step(cfg: ModelConfig, opt_cfg: OptConfig,
                    journal: bool = False):
    return partial(train_step, cfg=cfg, opt_cfg=opt_cfg, journal=journal)
