"""Training launcher.

Selects an architecture config (full or reduced), builds the replicated
Arcadia log + checkpoint stores, and runs the fault-tolerant Trainer.
On a CPU-only machine use --reduced (the full configs are exercised via
launch/dryrun.py, which never allocates).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --reduced \
      --steps 50 --batch 8 --seq 128 --ckpt-every 10 --journal-freq 4

Compiled programs are cached where ``JAX_COMPILATION_CACHE_DIR`` says,
or else in ``<checkout>/.jax_cache``.
"""

from __future__ import annotations

import argparse
import os
import time
from pathlib import Path
from typing import List, Optional, Tuple

from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                              FileStore, ObjectStore, ReplicatedStore)
from repro.configs import ARCH_NAMES, get_config, reduced_config
from repro.core import Log
from repro.core.replication import ReplicaSet, build_replica_set
from repro.data import DataConfig, SyntheticDataset
from repro.models.config import ModelConfig
from repro.optim import OptConfig
from repro.train.trainer import Trainer, TrainerConfig

CHECKOUT = Path(__file__).resolve().parents[3]


def use_compile_cache(root: Path = CHECKOUT) -> str:
    """Turn on JAX's persistent compilation cache and return its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is left for JAX to read;
    otherwise the cache is the fixed ``<root>/.jax_cache`` (the path is
    part of the cache key, so it must not move between runs)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-scale config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--optimizer", default="adamw",
                    choices=["adamw", "adafactor"])
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--journal-freq", type=int, default=4,
                    help="F for the frequency-based force policy")
    ap.add_argument("--log-backups", type=int, default=1)
    ap.add_argument("--store-replicas", type=int, default=2)
    ap.add_argument("--store-dir", default=None,
                    help="directory-backed stores instead of in-memory")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace) -> ModelConfig:
    return reduced_config(args.arch) if args.reduced else \
        get_config(args.arch)


def build_journal(args: argparse.Namespace
                  ) -> Tuple[ReplicaSet, ReplicatedStore]:
    """The replicated Arcadia log for manifests + journal, and the
    replicated checkpoint store."""
    rs = build_replica_set(
        mode="local+remote" if args.log_backups else "local",
        capacity=1 << 20, n_backups=args.log_backups,
        write_quorum=min(2, args.log_backups + 1))
    if args.store_dir:
        stores = [FileStore(f"{args.store_dir}/replica{i}", f"fs{i}")
                  for i in range(args.store_replicas)]
    else:
        stores = [ObjectStore(f"s{i}") for i in range(args.store_replicas)]
    rstore = ReplicatedStore(stores,
                             write_quorum=(args.store_replicas // 2) + 1)
    return rs, rstore


def make_trainer(args: argparse.Namespace, cfg: ModelConfig, log: Log,
                 rstore: ReplicatedStore) -> Trainer:
    """A trainer whose checkpoints commit through ``log``."""
    mgr = CheckpointManager(rstore, log,
                            CheckpointConfig(force_freq=args.journal_freq))
    data = SyntheticDataset(cfg, DataConfig(batch=args.batch,
                                            seq_len=args.seq,
                                            seed=args.seed))
    opt = OptConfig(name=args.optimizer, lr=args.lr, warmup_steps=5,
                    decay_steps=max(args.steps * 2, 100))
    return Trainer(cfg, opt, data, mgr,
                   TrainerConfig(total_steps=args.steps,
                                 ckpt_every=args.ckpt_every,
                                 journal_freq=args.journal_freq,
                                 seed=args.seed))


def main(argv: Optional[List[str]] = None) -> None:
    args = parse_args(argv)
    use_compile_cache()
    cfg = model_config(args)
    print(f"[train] arch={cfg.name} params={cfg.param_count()/1e6:.1f}M "
          f"(active {cfg.active_param_count()/1e6:.1f}M)")
    rs, rstore = build_journal(args)
    tr = make_trainer(args, cfg, rs.log, rstore)
    start = tr.init_or_restore()
    if start:
        print(f"[train] resumed from step {start} "
              f"(journal re-seated data at {tr.data.step})")
    t0 = time.time()
    rep = tr.run()
    dt = time.time() - t0
    print(f"[train] {rep.steps_run} steps in {dt:.1f}s "
          f"({rep.steps_run / max(dt, 1e-9):.2f} steps/s)")
    print(f"[train] loss {rep.losses[0]:.3f} -> {rep.losses[-1]:.3f}; "
          f"ckpts saved={rep.ckpts_saved} skipped={rep.ckpts_skipped}")
    print(f"[train] log stats: {rs.log.stats()}")


if __name__ == "__main__":
    main()
