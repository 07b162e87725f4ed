"""Checkpoint manager: save/restore, integrity, quorum, elastic restore,
bounded-loss frequency policy, GC."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import (CheckpointConfig, CheckpointManager,
                              ObjectStore, ReplicatedStore, ShardCorruptError)
from repro.core import Log, LogConfig, PMEMDevice, QuorumError
from repro.core.replication import build_replica_set

CAP = 1 << 18


def make_mgr(n_stores=3, store_quorum=2, log_backups=0, **cfg):
    stores = [ObjectStore(f"store{i}") for i in range(n_stores)]
    rstore = ReplicatedStore(stores, write_quorum=store_quorum)
    if log_backups:
        rs = build_replica_set(mode="local+remote", capacity=CAP,
                               n_backups=log_backups, write_quorum=2)
        log = rs.log
    else:
        dev = PMEMDevice(CAP + 4096)
        log = Log.create(dev, LogConfig(capacity=CAP))
    mgr = CheckpointManager(rstore, log, CheckpointConfig(**cfg))
    return mgr, stores, log


def make_state(seed=0, dim=32):
    rng = np.random.default_rng(seed)
    return {
        "params": {
            "embed": rng.normal(size=(dim, 8)).astype(np.float32),
            "layer": {"w": rng.normal(size=(8, 8)).astype(np.float32),
                      "b": np.zeros(8, np.float32)},
        },
        "opt": {"mu": rng.normal(size=(dim, 8)).astype(np.float32)},
        "step": np.int64(0),
    }


def assert_tree_equal(a, b):
    ja, jb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(ja) == len(jb)
    for x, y in zip(ja, jb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_save_restore_roundtrip():
    mgr, stores, log = make_mgr()
    state = make_state()
    mgr.save(10, state, extra={"data_pos": 1234}, sync=True)
    step, got, extra = mgr.restore(state)
    assert step == 10 and extra == {"data_pos": 1234}
    assert_tree_equal(got, state)


def test_restore_latest_of_many():
    mgr, stores, log = make_mgr()
    states = {s: make_state(seed=s) for s in (1, 2, 3)}
    for s, st in states.items():
        mgr.save(s, st, sync=True)
    step, got, _ = mgr.restore(states[1])
    assert step == 3
    assert_tree_equal(got, states[3])
    step, got, _ = mgr.restore(states[1], step=2)   # point-in-time
    assert step == 2
    assert_tree_equal(got, states[2])


def test_corrupt_shard_falls_back_to_replica_and_repairs():
    mgr, stores, log = make_mgr()
    state = make_state()
    mgr.save(1, state, sync=True)
    key = [k for k in stores[0].keys() if "embed" in k][0]
    stores[0].corrupt(key, seed=3)
    step, got, _ = mgr.restore(state)
    assert_tree_equal(got, state)                    # replica fallback
    # read-repair fixed replica 0
    assert stores[0].get(key) == stores[1].get(key)


def test_all_replicas_corrupt_falls_back_to_older_checkpoint():
    mgr, stores, log = make_mgr()
    s1, s2 = make_state(1), make_state(2)
    mgr.save(1, s1, sync=True)
    mgr.save(2, s2, sync=True)
    key = [k for k in stores[0].keys() if "step000000000002" in k][0]
    for st in stores:
        st.corrupt(key, seed=5)
    step, got, _ = mgr.restore(s1)
    assert step == 1                                  # graceful fallback
    assert_tree_equal(got, s1)


def test_torn_shard_write_detected():
    mgr, stores, log = make_mgr()
    state = make_state()
    mgr.save(1, state, sync=True)
    key = stores[0].keys()[0]
    n = len(stores[0].get(key))
    for st in stores:
        st.truncate(key, keep=n // 2)
    with pytest.raises(ShardCorruptError):
        mgr.restore(state)


def test_put_quorum():
    mgr, stores, log = make_mgr(n_stores=3, store_quorum=2)
    stores[2].dead = True
    mgr.save(1, make_state(), sync=True)              # 2/3 acks: ok
    stores[1].dead = True
    with pytest.raises(QuorumError):
        mgr.save(2, make_state(), sync=True)          # 1/3 acks: fail


def test_elastic_restore_different_chunk_count():
    """Checkpoint written with 4 writer chunks restores from a manager
    configured with 1 (different host count): shards reassemble."""
    stores = [ObjectStore("s0")]
    rstore = ReplicatedStore(stores, write_quorum=1)
    dev = PMEMDevice(CAP + 4096)
    log = Log.create(dev, LogConfig(capacity=CAP))
    w = CheckpointManager(rstore, log, CheckpointConfig(chunks_per_leaf=4))
    state = make_state(dim=64)
    w.save(7, state, sync=True)
    r = CheckpointManager(rstore, log, CheckpointConfig(chunks_per_leaf=1))
    step, got, _ = r.restore(state)
    assert step == 7
    assert_tree_equal(got, state)


def test_frequency_policy_bounded_loss():
    """Save every 'step' with freq F; after a crash, the restored step is
    within the F×T vulnerability window of the last saved step."""
    F = 4
    stores = [ObjectStore("s0")]
    rstore = ReplicatedStore(stores, write_quorum=1)
    dev = PMEMDevice(CAP + 4096, mode="strict")
    log = Log.create(dev, LogConfig(capacity=CAP, max_threads=1))
    mgr = CheckpointManager(rstore, log, CheckpointConfig(force_freq=F))
    state = make_state()
    last = 17
    for s in range(1, last + 1):
        mgr.save(s, state)
    # crash: only forced manifests survive
    survivor = dev.crash(np.random.default_rng(0), keep_probability=0.0)
    relog = Log.open(survivor, LogConfig(capacity=CAP))
    rmgr = CheckpointManager(rstore, relog, CheckpointConfig(force_freq=F))
    step, got, _ = rmgr.restore(state)
    bound = F * log.cfg.max_threads
    assert last - step <= bound, (step, last, bound)
    assert step == 16                      # last lsn divisible by F
    assert_tree_equal(got, state)


def test_journal_records_roundtrip():
    mgr, stores, log = make_mgr()
    mgr.save(1, make_state(), sync=True)
    for i in range(5):
        mgr.journal({"step": i, "loss": float(i) * 0.5}, sync=True)
    recs = mgr.journal_records()
    assert [r["step"] for _, r in recs] == list(range(5))


def test_gc_reclaims_old_checkpoints():
    mgr, stores, log = make_mgr(keep_last=2)
    state = make_state()
    for s in range(1, 6):
        mgr.save(s, state, sync=True)
    removed = mgr.gc()
    assert removed == 3
    assert [m["step"] for _, m in mgr.manifests()] == [4, 5]
    # shards of dropped checkpoints are gone
    assert not any("step000000000001" in k for k in stores[0].keys())
    # restore still works
    step, got, _ = mgr.restore(state)
    assert step == 5


def test_save_async_overlaps():
    mgr, stores, log = make_mgr()
    state = make_state()
    futs = [mgr.save_async(s, state) for s in (1, 2, 3)]
    mgr.wait()
    assert mgr.latest_step() == 3


def test_save_async_of_device_arrays_is_the_image_at_its_step():
    """Later updates make new arrays; the save keeps the ones it was
    given, so it restores bit-equal to the state at its step."""
    mgr, stores, log = make_mgr()
    state = jax.tree_util.tree_map(jnp.asarray, make_state(1))
    at_save = jax.device_get(state)
    mgr.save_async(7, state)
    for _ in range(3):
        state = jax.tree_util.tree_map(lambda x: x * 2 + 1, state)
    mgr.wait()
    step, got, _ = mgr.restore(at_save)
    assert step == 7
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(at_save)):
        assert a.dtype == b.dtype and a.tobytes() == b.tobytes()


def test_save_async_of_numpy_leaves_ignores_later_mutation():
    mgr, stores, log = make_mgr()
    state = make_state(2)
    before = jax.tree_util.tree_map(np.copy, state)
    mgr.save_async(3, state)
    state["params"]["embed"][:] = 0.0
    state["opt"]["mu"] += 1.0
    mgr.wait()
    _, got, _ = mgr.restore(before)
    assert_tree_equal(got, before)


def test_save_async_leaves_the_host_copy_to_the_save_worker(monkeypatch):
    """The caller only starts the copies: the snapshot holds the device
    arrays themselves, and the save worker fetches them."""
    import threading
    from repro.checkpoint import manager as mgr_mod
    fetched_on = []
    fetch = mgr_mod._fetch

    def recording_fetch(tree):
        fetched_on.append(threading.current_thread().name)
        return fetch(tree)

    monkeypatch.setattr(mgr_mod, "_fetch", recording_fetch)
    state = jax.tree_util.tree_map(jnp.asarray, make_state(3))
    snap = mgr_mod._snapshot(state)
    for a, b in zip(jax.tree_util.tree_leaves(snap),
                    jax.tree_util.tree_leaves(state)):
        assert isinstance(a, jax.Array) and a is b
    mgr, stores, log = make_mgr()
    mgr.save_async(4, state)
    mgr.wait()
    assert len(fetched_on) == 1
    assert fetched_on[0].startswith("ckpt-save")
    assert fetched_on[0] != threading.current_thread().name
    _, got, _ = mgr.restore(state)
    assert_tree_equal(got, state)
