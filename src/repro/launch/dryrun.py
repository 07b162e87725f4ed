"""Multi-pod dry-run: lower + compile every (arch × shape) cell for the
production meshes and extract roofline inputs from the compiled
artifact.  No arrays are ever allocated — inputs are ShapeDtypeStructs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b \
      --shape train_4k [--multi-pod] [--all] [--out artifacts/dryrun]

The FIRST two lines below must run before ANY other jax import: jax
locks the device count at first init, and the dry-run (only) needs 512
placeholder host devices.
"""

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import re
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ARCH_NAMES, SHAPES, applicable_shapes,
                           get_config, input_specs)
from repro.distributed.sharding import ShardingRules
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.config import ModelConfig
from repro.optim import OptConfig
from repro.train.step import make_train_step, train_state_specs

# hardware constants (TPU v5e), per chip
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link (intra-pod)

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "all-to-all", "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2,
                "u16": 2, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
                "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "token": 0}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([\d,]*)\]")


def _shape_bytes(s: str) -> int:
    m = _SHAPE_RE.match(s)
    if not m:
        return 0
    dt, dims = m.groups()
    b = _DTYPE_BYTES.get(dt)
    if b is None:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9]+\[[^=]*?)\s*"
    r"(all-gather-start|all-reduce-start|reduce-scatter|all-to-all|"
    r"collective-permute-start|all-gather|all-reduce|collective-permute)"
    r"\(")


def collective_bytes(hlo_text: str, top_k: int = 0) -> Dict[str, Any]:
    """Sum result-shape bytes of every collective op in compiled HLO.
    Shapes are per-device post-partitioning; multiply by device count
    for fleet totals.  '-done' ops are skipped (their '-start' twin is
    counted, using the destination element of the start tuple).
    With top_k > 0 also returns the largest individual ops (the
    hillclimbing targets)."""
    out: Dict[str, Any] = {k: 0 for k in COLLECTIVE_OPS}
    out["count"] = 0
    tops = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        shapes_str, op = m.groups()
        shapes = [f"{dt}[{dims}]" for dt, dims in
                  _SHAPE_RE.findall(shapes_str)]
        if not shapes:
            continue
        if op.endswith("-start"):
            op = op[: -len("-start")]
            nbytes = _shape_bytes(shapes[-1])    # destination buffer
            shape_repr = shapes[-1]
        else:
            nbytes = sum(_shape_bytes(s) for s in shapes)
            shape_repr = shapes[0]
        out[op] += nbytes
        out["count"] += 1
        if top_k:
            tops.append((nbytes, op, shape_repr))
    if top_k:
        tops.sort(reverse=True)
        out["top"] = [f"{op} {shape} ({b/1e9:.2f}GB)"
                      for b, op, shape in tops[:top_k]]
    return out


def build_cell(cfg: ModelConfig, shape_name: str, mesh,
               fsdp_axes=("data",), rule_overrides=None,
               journal: bool = False, moe_ep: bool = False,
               act_constraint: bool = False):
    """Returns (fn, args_specs, in_shardings, donate) for one cell."""
    from repro.models import layers as L
    if moe_ep:
        L.set_moe_ep(mesh, ("data", "model"))
        rule_overrides = dict(rule_overrides or {},
                              expert=((("data", "model"),)))
    if act_constraint:
        baxes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        M.set_activation_spec(P(baxes, None, None))
    shape = SHAPES[shape_name]
    rules = ShardingRules(mesh, fsdp_axes=fsdp_axes,
                          overrides=rule_overrides)
    cell = input_specs(cfg, shape)
    if cell["kind"] == "train":
        opt_cfg = OptConfig(
            name="adafactor" if cfg.param_count() > 30e9 else "adamw")
        state_specs = train_state_specs(cfg, opt_cfg)
        param_sh = rules.param_shardings(state_specs["params"])
        # optimizer leaves inherit the param leaf's PartitionSpec:
        # m/v are same-shape; adafactor vr drops the last dim, vc the
        # second-to-last.  Fall back to replication if a derived spec
        # no longer divides the (reduced) shape.
        pflat = jax.tree_util.tree_flatten_with_path(param_sh)[0]
        pspec = {jax.tree_util.keystr(p): s.spec for p, s in pflat}

        axis_sizes_ = dict(mesh.shape)

        def opt_sh(path, leaf):
            key = jax.tree_util.keystr(path)
            base = pspec.get(re.sub(r"\['(m|v|vr|vc)'\]$", "", key))
            if base is None:
                return NamedSharding(mesh, P())
            if not base and leaf.ndim >= 2 and "data" in axis_sizes_ and \
                    leaf.shape[0] % axis_sizes_["data"] == 0 and \
                    int(jnp.prod(jnp.array(leaf.shape))) >= 2 ** 16:
                # ZeRO-1: params replicated, optimizer state sharded
                return NamedSharding(mesh, P("data"))
            factored = key.endswith(("['vr']", "['vc']"))
            n = len(leaf.shape) + (1 if factored else 0)  # param ndim
            ent = list(base) + [None] * (n - len(base))
            if key.endswith("['vr']"):
                ent = ent[: n - 1]                  # param dim -1 dropped
            elif key.endswith("['vc']"):
                ent = ent[: n - 2] + [ent[n - 1]]   # param dim -2 dropped
            axis_sizes = dict(mesh.shape)
            for i, (dim, e) in enumerate(zip(leaf.shape, ent)):
                if e is None:
                    continue
                axes = e if isinstance(e, tuple) else (e,)
                k = 1
                for a in axes:
                    k *= axis_sizes[a]
                if dim % k:
                    ent[i] = None
            while ent and ent[-1] is None:
                ent.pop()
            return NamedSharding(mesh, P(*ent))

        oflat, otree = jax.tree_util.tree_flatten_with_path(
            state_specs["opt"])
        state_sh = {
            "params": param_sh,
            "opt": jax.tree_util.tree_unflatten(
                otree, [opt_sh(p, l) for p, l in oflat]),
            "step": NamedSharding(mesh, P()),
        }
        batch_sh = rules.input_shardings(cell["batch"])
        fn = make_train_step(cfg, opt_cfg, journal=journal)
        args = (state_specs, cell["batch"])
        in_sh = (state_sh, batch_sh)
        out_sh = (state_sh, None)
        donate = (0,)
        return fn, args, in_sh, out_sh, donate

    # serve cell
    pspecs = M.param_specs(cfg)
    param_sh = rules.param_shardings(pspecs)
    batch_sh = rules.input_shardings(cell["batch"])
    if cell["cache"] is not None:
        cache_sh = rules.cache_shardings(cell["cache"])
        idx_sh = NamedSharding(mesh, P())

        def fn(params, batch, cache, index):
            return M.serve_step(params, cfg, batch, cache, index)
        args = (pspecs, cell["batch"], cell["cache"], cell["index"])
        in_sh = (param_sh, batch_sh, cache_sh, idx_sh)
        out_sh = (None, cache_sh)
        donate = (2,)
        return fn, args, in_sh, out_sh, donate

    def fn(params, batch):                  # encoder prefill: no cache
        return M.serve_step(params, cfg, batch, None, None)
    args = (pspecs, cell["batch"])
    in_sh = (param_sh, batch_sh)
    return fn, args, in_sh, None, ()


def measure_block(cfg: ModelConfig, shape_name: str, mesh,
                  fsdp_axes=("data",), rule_overrides=None
                  ) -> Dict[str, Any]:
    """Compile ONE block standalone (same mesh/shardings) and read its
    cost analysis.  XLA counts while-loop bodies once, so the full-graph
    numbers understate the scanned stack by (n_blocks - 1) × block —
    run_cell uses this to correct the roofline totals."""
    shape = SHAPES[shape_name]
    # unroll inner (attention) scans so the block's HLO FLOPs are exact
    cfg = dataclasses.replace(cfg, scan_unroll=True)
    rules = ShardingRules(mesh, fsdp_axes=fsdp_axes,
                          overrides=rule_overrides)
    cell = input_specs(cfg, shape)
    # one block's params: strip the stacked leading dim
    full = M.param_specs(cfg)
    bspecs = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
        full["blocks"])
    bsh = rules.param_shardings(bspecs)
    bkey = next(k for k in cell["batch"] if k != "labels")
    B = cell["batch"][bkey].shape[0]
    S = sum(cell["batch"][k].shape[1] for k in cell["batch"]
            if k != "labels")
    h_spec = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                  jnp.dtype(cfg.compute_dtype))
    h_sh = rules.input_shardings({"h": h_spec})["h"]
    train = cell["kind"] == "train"
    if train:
        def fn(bp, h):
            def loss(bp, h):
                out, _, aux = M.apply_block(bp, h, cfg, None, None)
                return jnp.sum(out.astype(jnp.float32) ** 2) + aux["aux"]
            g = jax.grad(loss, argnums=(0, 1))(bp, h)
            return g
        args = (bspecs, h_spec)
        in_sh = (bsh, h_sh)
    else:
        bc = None
        if cell["cache"] is not None:
            bc = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct(s.shape[1:], s.dtype),
                cell["cache"]["blocks"])
            bc_sh = rules.cache_shardings(bc)

            def fn(bp, h, c, index):
                out, ncs, _ = M.apply_block(bp, h, cfg, c, index)
                return out, ncs
            args = (bspecs, h_spec, bc, cell["index"])
            in_sh = (bsh, h_sh, bc_sh, NamedSharding(mesh, P()))
        else:
            def fn(bp, h):
                out, _, _ = M.apply_block(bp, h, cfg, None, None)
                return out
            args = (bspecs, h_spec)
            in_sh = (bsh, h_sh)
    with mesh:
        compiled = jax.jit(fn, in_shardings=in_sh).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = collective_bytes(compiled.as_text(), top_k=6)
    return {"flops_per_device": cost.get("flops", 0.0),
            "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
            "collective_bytes_per_device": coll}


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             out_dir: Optional[str] = None,
             fsdp_axes=("data",), quiet: bool = False,
             unroll: bool = False,
             cfg_overrides: Optional[Dict[str, Any]] = None,
             rule_overrides: Optional[Dict[str, tuple]] = None,
             journal: bool = False, moe_ep: bool = False,
             act_constraint: bool = False,
             variant: str = "") -> Dict[str, Any]:
    cfg = get_config(arch)
    if unroll:
        cfg = dataclasses.replace(cfg, scan_unroll=True)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)
    tag = f"{arch}__{shape_name}__{mesh_name}" + ("__unroll" if unroll
                                                  else "")
    if variant:
        tag += f"__{variant}"
    if shape_name not in applicable_shapes(cfg):
        return {"cell": tag, "status": "skip",
                "reason": "shape not applicable (DESIGN.md §4)"}
    t0 = time.time()
    fn, args, in_sh, out_sh, donate = build_cell(
        cfg, shape_name, mesh, fsdp_axes, rule_overrides=rule_overrides,
        journal=journal, moe_ep=moe_ep, act_constraint=act_constraint)
    with mesh:
        jfn = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                      donate_argnums=donate)
        lowered = jfn.lower(*args)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo, top_k=6)
    result = {
        "cell": tag, "status": "ok", "arch": arch, "shape": shape_name,
        "mesh": mesh_name, "n_devices": n_dev,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "flops_per_device": cost.get("flops", 0.0),
        "bytes_accessed_per_device": cost.get("bytes accessed", 0.0),
        "collective_bytes_per_device": coll,
        "memory_analysis": {
            k: getattr(mem, k) for k in
            ("argument_size_in_bytes", "output_size_in_bytes",
             "temp_size_in_bytes", "generated_code_size_in_bytes")
            if hasattr(mem, k)
        },
        "model_params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
    }
    if not unroll and cfg.n_blocks > 1:
        # XLA cost analysis counts while bodies ONCE: correct the scanned
        # stack by adding (n_blocks - 1) × one standalone block's cost.
        if moe_ep:
            rule_overrides = dict(rule_overrides or {},
                                  expert=((("data", "model"),)))
        blk = measure_block(cfg, shape_name, mesh, fsdp_axes,
                            rule_overrides=rule_overrides)
        nb = cfg.n_blocks
        result["block"] = blk
        result["n_blocks"] = nb
        result["flops_per_device_corrected"] = (
            result["flops_per_device"] + (nb - 1) * blk["flops_per_device"])
        result["bytes_accessed_per_device_corrected"] = (
            result["bytes_accessed_per_device"]
            + (nb - 1) * blk["bytes_accessed_per_device"])
        cc = dict(result["collective_bytes_per_device"])
        for k, vv in blk["collective_bytes_per_device"].items():
            if k == "top":
                continue
            cc[k] = cc.get(k, 0) + (nb - 1) * vv
        result["collective_bytes_per_device_corrected"] = cc
    if not quiet:
        print(f"[dryrun] {tag}: compile {t_compile:.1f}s, "
              f"flops/dev={result['flops_per_device']:.3e}, "
              f"coll={sum(v for k, v in coll.items() if isinstance(v, (int, float)) and k != 'count'):.3e}B"
              f" ({coll['count']} ops)")
        print(f"  memory_analysis: {result['memory_analysis']}")
    if moe_ep:
        from repro.models import layers as L
        L.set_moe_ep(None, None)
    if act_constraint:
        M.set_activation_spec(None)
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, tag + ".json"), "w") as f:
            json.dump(result, f, indent=1)
    return result


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=ARCH_NAMES + [None])
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true",
                    help="every (arch × applicable shape) cell")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--fsdp-pods", action="store_true",
                    help="extend FSDP over the pod axis")
    ap.add_argument("--unroll", action="store_true",
                    help="unroll block scan for exact HLO FLOP counts")
    args = ap.parse_args()

    fsdp = ("pod", "data") if args.fsdp_pods else ("data",)
    cells = []
    if args.all:
        for arch in ARCH_NAMES:
            for shape in ["train_4k", "prefill_32k", "decode_32k",
                          "long_500k"]:
                cells.append((arch, shape))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape required unless --all")
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        try:
            r = run_cell(arch, shape, args.multi_pod, args.out, fsdp,
                         unroll=args.unroll)
            if r["status"] == "skip":
                print(f"[dryrun] {r['cell']}: SKIP ({r['reason']})")
        except Exception as e:
            failures += 1
            print(f"[dryrun] {arch}/{shape}: FAIL {type(e).__name__}: {e}")
            traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()
