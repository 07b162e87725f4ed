#!/usr/bin/env python3
"""Bring-up check on one TPU: the replicated log and its checkpoint journal.

Two phases run in this one process, on one chip, from ``--seed``:

  log      A replicated log as a deployment holds it: ``local+remote``,
           2 backups, write quorum 2, a 512 MiB ring per replica.  At
           least 256 MiB of payload goes in through ``Log.append_batch``
           in records of 1-4 MiB, so every record is FLAG_PHASH and is
           hashed by the Pallas kernel on the chip; each batch is forced
           to quorum.  The primary's device then crashes and ``Log.open``
           recovers it (the recovery scan validates every record in one
           batched kernel call).  Every acknowledged record must read
           back byte-equal from the primary and from both backups, and
           every hash the chip computed must equal the host NumPy
           evaluation.
  journal  The main path of ``repro.launch.train`` at the full width of
           mamba2-130m: 6 steps of 4x2048 tokens with a checkpoint at
           step 3 committed through the replicated log.  The trainer is
           thrown away, the log's primary crashes and is reopened, and a
           new trainer restores with ``init_or_restore`` and runs the
           remaining steps.  The restored state must be bit-equal to the
           saved one, the losses after the restore must equal an
           uninterrupted run's, and all losses must be finite.

Each phase prints one JSON line (device kind, compile and wall seconds,
sizes, ``peak_bytes_in_use``); the last line is
``{"ok": true, "device": {...}}``.  Without a TPU, or when any check
fails, it exits non-zero and prints no result.

    python chip_smoke.py --seed 0
"""

from __future__ import annotations

import argparse
import json
import os
import struct
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MIB = 1 << 20
# record header on media: lsn, size, crc, flags (repro.core.log)
REC_HDR = struct.Struct("<QIIQ")
SEED_HDR = struct.Struct("<QI")       # (lsn, size) prefix of the hash

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            self.total += duration


class CheckFailed(Exception):
    pass


def check(cond, msg):
    """A result check that holds under ``python -O`` too."""
    if not cond:
        raise CheckFailed(msg)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _phash_lanes(records):
    """(lsn, payload) pairs -> the (seed || payload) uint32 lane matrix
    the log hashes (zero-padded rows)."""
    width = max((SEED_HDR.size + len(p) + 3) // 4 for _, p in records)
    mat = np.zeros((len(records), width), np.uint32)
    u8 = mat.view(np.uint8)
    for i, (lsn, p) in enumerate(records):
        u8[i, :SEED_HDR.size] = np.frombuffer(SEED_HDR.pack(lsn, len(p)),
                                              np.uint8)
        u8[i, SEED_HDR.size:SEED_HDR.size + len(p)] = np.frombuffer(
            p, np.uint8)
    return mat


def _check_readback(log, expect, who):
    got = dict(log.iter_records())
    check(sorted(got) == sorted(expect),
          f"{who}: recovered LSNs differ from the acknowledged ones")
    for lsn, payload in expect.items():
        check(got[lsn] == payload, f"{who}: record {lsn} differs")


def log_phase(seed, ring=512 * MIB, payload=256 * MIB, batch=8,
              min_rec=MIB, max_rec=4 * MIB):
    from repro.core import Log, LogConfig, build_replica_set
    from repro.core.log import FLAG_PHASH, ring_offset
    from repro.kernels.checksum.ops import tensor_checksum_batch

    rng = np.random.default_rng(seed)
    sizes = []
    while sum(sizes) < payload:
        sizes.append(int(rng.integers(min_rec, max_rec + 1)))
    blob = memoryview(rng.bytes(sum(sizes)))
    offs = np.concatenate([[0], np.cumsum(sizes)])
    payloads = [blob[offs[i]:offs[i + 1]] for i in range(len(sizes))]

    rs = build_replica_set(mode="local+remote", capacity=ring, n_backups=2,
                           write_quorum=2)
    try:
        check(rs.cfg.phash_threshold <= min_rec, "records below the "
              "kernel hash threshold")
        t0 = time.perf_counter()
        lsns = []
        for i in range(0, len(payloads), batch):
            lsns += rs.log.append_batch(payloads[i:i + batch], freq=1)
        append_s = time.perf_counter() - t0
        check(rs.log.durable_lsn >= lsns[-1], "batches not forced")
        rs.group.drain()
        expect = dict(zip(lsns, payloads))

        # every hash the chip wrote into a header equals the NumPy one
        raw = rs.primary_dev.read(ring_offset(), ring)
        hdr_crc, off = [], 0
        for lsn, p in expect.items():
            got_lsn, size, crc, flags = REC_HDR.unpack_from(raw, off)
            check((got_lsn, size) == (lsn, len(p)) and flags & FLAG_PHASH,
                  f"header of record {lsn}")
            hdr_crc.append(crc)
            off += (REC_HDR.size + size + 7) & ~7
        del raw
        mat = _phash_lanes(list(expect.items()))
        host = tensor_checksum_batch(mat, use_pallas=False)
        chip = np.asarray(tensor_checksum_batch(mat), np.uint32)
        check(np.array_equal(np.asarray(hdr_crc, np.uint32), host),
              "append-time chip hashes differ from NumPy")
        check(np.array_equal(chip, host),
              "batched chip hashes differ from NumPy")
        del mat

        t0 = time.perf_counter()
        relog = Log.open(rs.primary_dev.crash(), rs.cfg, repl=rs.group)
        recover_s = time.perf_counter() - t0
        _check_readback(relog, expect, "primary")
        for srv in rs.servers:
            _check_readback(Log.open(srv.device, LogConfig(capacity=ring)),
                            expect, srv.server_id)
    finally:
        rs.shutdown()
    return dict(records=len(lsns), bytes=int(sum(sizes)),
                append_s=append_s, recover_s=recover_s,
                replicas_checked=1 + len(rs.servers))


def _host_state(state):
    import jax
    return [np.asarray(x) for x in
            jax.tree_util.tree_leaves(jax.device_get(state))]


def journal_phase(seed, arch="mamba2-130m", batch=4, seq=2048, steps=6,
                  ckpt_at=3, reduced=False):
    from repro.core import Log
    from repro.launch import train

    argv = ["--arch", arch, "--steps", str(steps), "--batch", str(batch),
            "--seq", str(seq), "--ckpt-every", str(ckpt_at),
            "--log-backups", "2", "--seed", str(seed)]
    args = train.parse_args(argv + (["--reduced"] if reduced else []))
    cfg = train.model_config(args)

    # uninterrupted reference run, on a journal of its own
    rs, rstore = train.build_journal(args)
    tr = train.make_trainer(args, cfg, rs.log, rstore)
    check(tr.init_or_restore() == 0, "fresh journal restored a step")
    t0 = time.perf_counter()
    ref_losses = list(tr.run().losses)
    ref_s = time.perf_counter() - t0
    tr.mgr.close()
    rs.shutdown()
    del tr

    # interrupted run: checkpoint at ckpt_at, crash, restore, finish
    rs, rstore = train.build_journal(args)
    try:
        tr = train.make_trainer(args, cfg, rs.log, rstore)
        check(tr.init_or_restore() == 0, "fresh journal restored a step")
        tr.run(n_steps=ckpt_at)
        losses = list(tr.report.losses)
        saved = _host_state(tr.state)
        tr.mgr.close()
        del tr
        relog = Log.open(rs.primary_dev.crash(), rs.cfg, repl=rs.group)
        tr = train.make_trainer(args, cfg, relog, rstore)
        t0 = time.perf_counter()
        start = tr.init_or_restore()
        restore_s = time.perf_counter() - t0
        check(start == ckpt_at, f"restored step {start} != {ckpt_at}")
        restored = _host_state(tr.state)
        check(len(restored) == len(saved), "restored state differs")
        for a, b in zip(saved, restored):
            check(a.dtype == b.dtype and a.shape == b.shape
                  and a.tobytes() == b.tobytes(), "restored state differs")
        losses += tr.run().losses
        tr.mgr.close()
    finally:
        rs.shutdown()
    check(len(losses) == steps and np.all(np.isfinite(losses)),
          f"losses {losses}")
    check(losses == ref_losses,
          f"losses after restore {losses} != uninterrupted {ref_losses}")
    return dict(arch=cfg.name, params=cfg.param_count(), steps=steps,
                tokens_per_step=batch * seq, restored_step=start,
                losses=losses, uninterrupted_s=ref_s, restore_s=restore_s)


def lowers_to_kernel():
    """Whether the checksum and SSD dispatchers lower to a Mosaic kernel
    (``tpu_custom_call``) on this backend, at mamba2-130m widths."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.checksum import ops as cksum
    from repro.kernels.ssd_scan import ops as ssd

    spec = jax.ShapeDtypeStruct
    ck = jax.jit(cksum.tensor_checksum).lower(spec((MIB,), jnp.uint8))
    B, S, H, P, N = 1, 2048, 24, 64, 128
    sd = jax.jit(ssd.ssd, static_argnames="chunk").lower(
        spec((B, S, H, P), jnp.bfloat16), spec((B, S, H), jnp.float32),
        spec((H,), jnp.float32), spec((B, S, 1, N), jnp.bfloat16),
        spec((B, S, 1, N), jnp.bfloat16), chunk=256)
    return {"checksum": "tpu_custom_call" in ck.as_text(),
            "ssd": "tpu_custom_call" in sd.as_text()}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {backend!r}",
              file=sys.stderr)
        return 1
    from repro.launch.train import use_compile_cache
    cache = use_compile_cache(ROOT)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    kernels = lowers_to_kernel()
    print(json.dumps({"phase": "lowering", "device_kind": dev.device_kind,
                      "tpu_custom_call": kernels, "compile_cache": cache}),
          flush=True)
    check(all(kernels.values()), f"a kernel fell back: {kernels}")

    for name, phase in (("log", log_phase), ("journal", journal_phase)):
        c0, t0 = clock.total, time.perf_counter()
        out = phase(args.seed)
        print(json.dumps({"phase": name, "passed": True,
                          "device_kind": dev.device_kind,
                          "compile_s": clock.total - c0,
                          "wall_s": time.perf_counter() - t0,
                          **out, "size_cuts": [],
                          "peak_bytes_in_use": _peak_bytes()}),
              flush=True)

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
