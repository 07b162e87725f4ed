"""Fault-tolerant trainer: the Arcadia log as the training journal.

Per step the trainer journals (step, loss, data-pipeline position) to
the log under the frequency-based force policy; every ``ckpt_every``
steps it saves a checkpoint through the log-backed manager, *async* so
shard writes overlap the next steps' compute (reserve/copy/complete
concurrency — §4.1).  Fault tolerance:

  * crash/restart  — restore the newest committed checkpoint, then
    replay the journal to re-seat the data pipeline at the exact batch;
    bounded loss: F×T journal records (§4.4).
  * straggler mitigation — an async save still in flight when the next
    checkpoint is due is *skipped over* (counted), so one slow writer
    group never stalls the step loop; at the store level the W<N quorum
    already tolerates a slow replica.
  * elastic restore — checkpoints reassemble from chunks, so a run
    checkpointed with N writer groups restores onto M (and onto a
    different mesh via device_put with the new shardings).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..checkpoint import CheckpointManager
from ..data import SyntheticDataset
from ..models.config import ModelConfig
from ..optim import OptConfig
from .step import init_train_state, make_train_step, train_state_specs


# the step's metrics read back each step, in one transfer
_READ = ("loss", obs.MOE_ROWS, obs.MOE_MAX_ROWS)


@dataclass
class TrainerConfig:
    total_steps: int = 100
    ckpt_every: int = 10
    journal_freq: int = 4        # F for journal force policy
    journal_every: int = 1       # journal a record every k steps
    seed: int = 0
    async_ckpt: bool = True


@dataclass
class TrainerReport:
    steps_run: int = 0
    losses: List[float] = field(default_factory=list)
    ckpts_saved: int = 0
    ckpts_skipped: int = 0       # straggler mitigation skips
    moe_rows: int = 0            # rows the held experts computed
    moe_max_rows: int = 0        # the largest held expert's rows, per
                                 # step and expert layer, summed
    restarts: int = 0
    restored_step: Optional[int] = None


class Trainer:
    def __init__(self, cfg: ModelConfig, opt_cfg: OptConfig,
                 dataset: SyntheticDataset, mgr: CheckpointManager,
                 tcfg: TrainerConfig,
                 shardings: Optional[Any] = None):
        self.cfg = cfg
        self.opt_cfg = opt_cfg
        self.data = dataset
        self.mgr = mgr
        self.tcfg = tcfg
        self.report = TrainerReport()
        # Without donation a step leaves its input state intact, so an
        # async save may hold the state's arrays by reference while later
        # steps run (checkpoint.manager._snapshot).  A state too large
        # to be held twice is donated, and a save then fetches it to the
        # host before the next step.
        self.donates = _donates(cfg, opt_cfg)
        self._step_fn = jax.jit(make_train_step(cfg, opt_cfg),
                                donate_argnums=(0,) if self.donates else ())
        self._pending_save = None
        self.state = None

    # ------------------------------------------------------------------ #
    def init_or_restore(self) -> int:
        """Fresh init, or restore newest checkpoint + journal replay."""
        template = init_train_state(jax.random.key(self.tcfg.seed),
                                    self.cfg, self.opt_cfg)
        try:
            step, state, extra = self.mgr.restore(template)
        except FileNotFoundError:
            self.state = template
            return 0
        self.state = jax.tree_util.tree_map(jnp.asarray, state)
        self.report.restored_step = step
        self.report.restarts += 1
        # journal replay: find the newest durable data position
        data_pos = extra.get("data_state", {"seed": self.data.cfg.seed,
                                            "step": step})
        for _, rec in self.mgr.journal_records():
            if rec.get("step", -1) >= data_pos["step"]:
                data_pos = {"seed": self.data.cfg.seed,
                            "step": rec["step"] + 1}
        self.data.restore(data_pos)
        return step

    # ------------------------------------------------------------------ #
    def run(self, n_steps: Optional[int] = None) -> TrainerReport:
        start = int(self.state["step"])
        end = min(self.tcfg.total_steps,
                  start + (n_steps or self.tcfg.total_steps))
        for s in range(start, end):
            with obs.span(obs.TRAIN_BATCH):
                batch = {k: jnp.asarray(v)
                         for k, v in self.data.batch_at(s).items()}
            self.data.step = s + 1
            with obs.span(obs.TRAIN_STEP):
                self.state, metrics = self._step_fn(self.state, batch)
            with obs.span(obs.TRAIN_LOSS):
                got = jax.device_get({k: metrics[k] for k in _READ
                                      if k in metrics})
            loss = float(got["loss"])
            if obs.MOE_ROWS in got:
                self.report.moe_rows += int(got[obs.MOE_ROWS])
                self.report.moe_max_rows += int(got[obs.MOE_MAX_ROWS])
            self.report.losses.append(loss)
            self.report.steps_run += 1
            if s % self.tcfg.journal_every == 0:
                with obs.span(obs.TRAIN_JOURNAL):
                    self.mgr.journal({"step": s, "loss": loss},
                                     sync=False)
            if (s + 1) % self.tcfg.ckpt_every == 0:
                self._checkpoint(s + 1)
        # end-of-run: drain outstanding writes, force the journal
        self._drain()
        return self.report

    def _checkpoint(self, step: int) -> None:
        extra = {"data_state": self.data.state()}
        if self.tcfg.async_ckpt:
            if self._pending_save is not None and \
                    not self._pending_save.done():
                self.report.ckpts_skipped += 1   # straggler: skip over
                return
            self._pending_save = self.mgr.save_async(
                step, self.state, extra, fetch=self.donates)
        else:
            self.mgr.save(step, self.state, extra, sync=True)
        self.report.ckpts_saved += 1

    def _drain(self) -> None:
        self.mgr.wait()
        last = self.mgr.log.next_lsn - 1
        if last >= 1 and self.mgr.log.durable_lsn < last:
            self.mgr.log.force(last, freq=1)


def _donates(cfg: ModelConfig, opt_cfg: OptConfig) -> bool:
    """Whether two copies of the train state would take more than half
    of the device's memory (which a CPU does not report)."""
    limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
    if not limit:
        return False
    nbytes = sum(x.size * x.dtype.itemsize for x in
                 jax.tree_util.tree_leaves(train_state_specs(cfg, opt_cfg)))
    return 2 * nbytes > limit // 2
