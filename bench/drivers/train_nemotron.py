"""Training one pipeline stage of Nemotron-H through the checkpoint
journal: ``Trainer.run`` of the journaled model, async checkpoints every
``ckpt_every`` steps committed through the replicated log, the journal
forced every F records.

The trainer is the program's own (``repro.launch.train.build_journal``
and ``make_trainer``); the benchmark gives it weights and token batches
made from the seed (``bench/lib/nemotron_h_ref.py``), so that the plain
reference starts from the same state without taking anything the
program made.  Set-up drives the trainer through its first three steps
with the window's own call (``Trainer.run``) and keeps the readings the
comparison needs; the window then continues the same trainer for whole
checkpoint periods and ends on a checkpoint, which it drains.
"""

from __future__ import annotations

import gc
import json
import sys
import time
from types import SimpleNamespace

import numpy as np

from bench.lib import nemotron_h_ref as ref
from bench.lib.common import Cell, Check, SpanLog, WindowResult, seed_words

FIRST_STEPS = 3
# configuration key -> the program's ModelConfig attribute
MODEL_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "vocab_size": "vocab_size",
              "hybrid_override_pattern": "layer_pattern",
              "ssm_state_size": "ssm_state_dim",
              "mamba_head_dim": "ssm_head_dim",
              "mamba_num_heads": "ssm_n_heads",
              "conv_kernel": "ssm_conv_width", "chunk_size": "ssm_chunk",
              "n_groups": "ssm_n_groups",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "intermediate_size": "d_ff",
              "moe_intermediate_size": "moe_d_ff",
              "moe_shared_expert_intermediate_size": "moe_shared_d_ff",
              "n_shared_experts": "n_shared_experts",
              "num_experts_per_tok": "experts_per_token",
              "n_routed_experts": "experts_held",
              "n_routed_experts_published": "n_experts",
              "expert_offset": "expert_offset",
              "routed_scaling_factor": "routed_scaling",
              "layer_norm_epsilon": "norm_eps",
              "tie_word_embeddings": "tie_embeddings",
              "param_dtype": "param_dtype",
              "compute_dtype": "compute_dtype"}
# the block as the reference writes it (nemotron_h_ref)
MECHANISMS = {"router_score": "sigmoid", "ssm_gate_first": True,
              "use_rope": False, "gated_mlp": False, "mlp_act": "relu2",
              "router_aux_weight": 0.0}


class BenchData:
    """The trainer's data source: ``nemotron_h_ref.make_batch`` batches."""

    def __init__(self, model: dict, seed: int, batch: int, seq: int):
        self.model, self.seed = model, seed
        self.batch, self.seq = batch, seq
        self.cfg = SimpleNamespace(seed=seed)
        self.step = 0

    def state(self):
        return {"seed": self.seed, "step": self.step}

    def restore(self, state):
        self.step = int(state["step"])

    def batch_at(self, step: int):
        return ref.make_batch(self.model, self.seed, step, self.batch,
                              self.seq)


def _counts(report) -> np.ndarray:
    return np.array([report.ckpts_saved, report.ckpts_skipped,
                     report.moe_rows, report.moe_max_rows])


class Driver:
    def __init__(self, cell: Cell, spans: SpanLog):
        self.cell = cell
        self.spans = spans
        self.p = cell.params
        self.model = cell.config
        self.opt = cell.config["optimizer"]

    # -- set-up ---------------------------------------------------------- #
    def _args(self):
        from repro.launch import train
        p, j = self.p, self.cell.config["journal"]
        argv = ["--arch", p["arch"], "--steps", str(p["total_steps"]),
                "--batch", str(p["batch"]), "--seq", str(p["seq"]),
                "--ckpt-every", str(p["ckpt_every"]),
                "--journal-freq", str(j["force_freq"]),
                "--log-backups", str(j["log_backups"]),
                "--store-replicas", str(j["store_replicas"]),
                "--lr", str(self.opt["lr"]),
                "--seed", str(int(seed_words(self.cell.seed, 1)[0] >> 1))]
        return train.parse_args(argv + (["--reduced"] if p.get("reduced")
                                        else []))

    def _check_program(self, mcfg, opt_cfg) -> None:
        want = {attr: self.model[k] for k, attr in MODEL_KEYS.items()}
        for attr, v in dict(want, **MECHANISMS).items():
            if getattr(mcfg, attr) != v:
                raise ValueError(f"program's {attr}={getattr(mcfg, attr)!r}"
                                 f" differs from the configuration's {v!r}")
        for k, v in self.opt.items():
            if getattr(opt_cfg, k) != v:
                raise ValueError(f"program's optimizer {k}="
                                 f"{getattr(opt_cfg, k)!r} != {v!r}")

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.launch import train
        from repro.optim import init_opt_state

        p, span = self.p, self.spans.span
        args = self._args()
        mcfg = train.model_config(args)
        self.rs, self.rstore = train.build_journal(args)
        tr = train.make_trainer(args, mcfg, self.rs.log, self.rstore)
        self._check_program(mcfg, tr.opt_cfg)
        self.tr = tr
        params0 = ref.make_params(self.model, self.cell.seed)
        # a step that donates its state deletes these arrays: the change
        # over the first steps is taken from a host copy
        host0 = jax.device_get(params0)
        tr.state = {"params": params0,
                    "opt": init_opt_state(params0, tr.opt_cfg),
                    "step": jnp.zeros((), jnp.int32)}
        del params0
        tr.data = BenchData(self.model, self.cell.seed, p["batch"],
                            p["seq"])
        step_fn, save_async = tr._step_fn, tr.mgr.save_async

        def step(state, batch):
            with span("bench.train_step"):
                return step_fn(state, batch)

        def save(*a, **kw):
            with span("bench.save", keep=True):
                return save_async(*a, **kw)

        tr._step_fn = step
        tr.mgr.save_async = save

        norms = jax.jit(lambda t: jax.tree_util.tree_map(
            lambda x: jnp.sqrt(jnp.sum(jnp.square(x))), t))
        delta = jax.jit(lambda a, b: norms(jax.tree_util.tree_map(
            jnp.subtract, a, b)))
        b1 = tr.opt_cfg.b1
        tr.run(n_steps=1)
        m = jax.tree_util.tree_map(lambda s: s["m"], tr.state["opt"],
                                   is_leaf=lambda s: isinstance(s, dict)
                                   and "m" in s)
        grad = {k: float(v) / (1 - b1) for k, v in
                _flat(jax.device_get(norms(m))).items()}
        del m
        t0 = time.perf_counter()
        tr.run(n_steps=FIRST_STEPS - 1)
        self.step_wall_s = (time.perf_counter() - t0) / (FIRST_STEPS - 1)
        change = jax.device_get(delta(tr.state["params"],
                                      jax.device_put(host0)))
        self.readings = {
            "losses": list(tr.report.losses[:FIRST_STEPS]),
            "grad": grad, "change": _flat(change),
            "rows": tr.report.moe_rows, "max_rows": tr.report.moe_max_rows}
        del host0, change
        self.start_step = int(tr.state["step"])
        self.tokens_per_step = p["batch"] * p["seq"]

    # -- window ---------------------------------------------------------- #
    def window_steps(self, seconds: float) -> int:
        """Whole checkpoint periods that fill about ``seconds`` at the
        configuration's step time, ending on a checkpoint."""
        n = self.p["ckpt_every"]
        periods = max(1, round(seconds / (n * self.p["step_s"])))
        end = (self.start_step // n + periods) * n
        return end - self.start_step

    def window(self, seconds: float) -> WindowResult:
        k = self.window_steps(seconds)
        c0 = _counts(self.tr.report)
        t0 = time.perf_counter()
        rep = self.tr.run(n_steps=k)
        dt = time.perf_counter() - t0
        saved, skipped, rows, max_rows = (int(x) for x in
                                          _counts(rep) - c0)
        done = rep.steps_run - FIRST_STEPS
        self.end_step = int(self.tr.state["step"])
        tokens = done * self.tokens_per_step
        counters = {"steps": done, "tokens": tokens, "window_s": dt,
                    "saves": len(self.spans.durations.get("bench.save", [])),
                    "ckpts_saved": saved, "ckpts_skipped": skipped,
                    "moe_rows": rows, "moe_max_rows": max_rows}
        # the counters of every run, traced or not, beside its checks,
        # with the wall time of a set-up step (no save in it)
        print("window " + json.dumps(dict(counters,
                                          setup_step_s=self.step_wall_s)),
              file=sys.stderr)
        return WindowResult({"train_tokens_per_s": tokens / dt},
                            attempted=k, failed=k - done, counters=counters)

    def free(self) -> None:
        import jax
        leaves, self.treedef = jax.tree_util.tree_flatten(self.tr.state)
        self.final = [np.asarray(x) for x in jax.device_get(leaves)]
        self.tr.state = None
        del leaves

    # -- comparison ------------------------------------------------------ #
    def check(self):
        import jax
        from repro.checkpoint import CheckpointManager
        from repro.core import Log
        tr, rs = self.tr, self.rs
        tr.mgr.close()
        losses = list(tr.report.losses)
        relog = Log.open(rs.primary_dev.crash(), rs.cfg, repl=rs.group)
        mgr = CheckpointManager(self.rstore, relog)
        template = jax.tree_util.tree_unflatten(
            self.treedef, [jax.ShapeDtypeStruct(a.shape, a.dtype)
                           for a in self.final])
        step, state, _ = mgr.restore(template)
        got = jax.tree_util.tree_leaves(state)
        differ = int(step != self.end_step) + sum(
            1 for a, b in zip(self.final, got)
            if a.dtype != b.dtype or a.shape != b.shape
            or a.tobytes() != b.tobytes())
        journal = {r["step"]: r["loss"] for _, r in mgr.journal_records()}
        jmiss = sum(1 for s, l in enumerate(losses) if journal.get(s) != l)
        mgr.close()
        rs.shutdown()
        self.final = state = got = None
        gc.collect()
        r = ref.reference_run(self.model, self.opt, self.cell.seed,
                              self.p["batch"], self.p["seq"], FIRST_STEPS)
        c = ref.compared(self.readings, r)
        lim = self.cell.config["limits"]
        return [Check(k, c[k], lim[k]) for k in lim] + [
            Check("restored_leaves_differ", differ, 0),
            Check("journal_losses_missing", jmiss, 0)]

    def close(self) -> None:
        pass


def _flat(tree):
    import jax
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(x) for p, x in flat}
