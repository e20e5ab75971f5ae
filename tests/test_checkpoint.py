"""Checkpoint manager: atomicity, bf16 round-trip, retention, async save —
plus the snapshot store backing scale-to-zero provisioning."""
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpointing import (
    CheckpointManager,
    CheckpointSaveError,
    SnapshotIntegrityError,
    SnapshotStore,
    snapshot_digest,
)


def make_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {
            "w": jax.random.normal(k, (8, 16)).astype(jnp.bfloat16),
            "b": jnp.arange(16, dtype=jnp.float32),
        },
        "opt": {"step": jnp.int32(7), "m": jnp.ones((8, 16), jnp.float32)},
    }


def assert_tree_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
        assert x.dtype == y.dtype


def test_roundtrip_exact_including_bf16(tmp_path):
    m = CheckpointManager(str(tmp_path))
    state = make_state()
    m.save(3, state)
    restored = m.restore(state, 3)
    assert_tree_equal(state, restored)


def test_latest_and_retention(tmp_path):
    m = CheckpointManager(str(tmp_path), retain=2)
    state = make_state()
    for s in (1, 2, 3, 4):
        m.save(s, state)
    assert m.latest_step() == 4
    assert m.all_steps() == [3, 4]  # older ones pruned


def test_no_tmp_dirs_left_behind(tmp_path):
    m = CheckpointManager(str(tmp_path))
    m.save(1, make_state())
    assert not [d for d in os.listdir(tmp_path) if d.endswith(".tmp")]


def test_restore_missing_raises(tmp_path):
    m = CheckpointManager(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        m.restore(make_state())


def test_async_save_then_restore(tmp_path):
    m = CheckpointManager(str(tmp_path), async_save=True)
    state = make_state()
    m.save(10, state)
    m.wait()
    assert m.latest_step() == 10
    assert_tree_equal(state, m.restore(state, 10))


def test_restore_into_structs(tmp_path):
    """Elastic restore: the 'like' tree can be ShapeDtypeStructs (a fresh job
    that never materialized params restores straight from disk)."""
    m = CheckpointManager(str(tmp_path))
    state = make_state()
    m.save(2, state)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    restored = m.restore(like, 2)
    assert_tree_equal(state, restored)


# --------------------------------------------------------- async save errors


def _failing_writer(path, **arrays):
    raise OSError("disk full (injected)")


def test_async_save_failure_surfaces_on_wait(tmp_path):
    """A worker-thread save failure must not vanish: wait() raises it."""
    m = CheckpointManager(str(tmp_path), async_save=True, writer=_failing_writer)
    m.save(1, make_state())
    with pytest.raises(CheckpointSaveError, match="disk full"):
        m.wait()
    # surfaced once: the caller was told, the manager is usable again
    m.wait()


def test_async_save_failure_surfaces_on_latest_step(tmp_path):
    """A loop that never calls wait() still hears about the dead save the
    moment it asks which step is current — the failed step must not let an
    older checkpoint masquerade as latest."""
    m = CheckpointManager(str(tmp_path), async_save=True, writer=_failing_writer)
    m.save(5, make_state())
    m._save_thread.join()  # let the worker die without consuming the error
    with pytest.raises(CheckpointSaveError):
        m.latest_step()


def test_async_save_failure_then_next_save_succeeds(tmp_path):
    """Transient failure: the next save() surfaces the old error, and a
    recovered writer persists normally afterwards."""
    m = CheckpointManager(str(tmp_path), async_save=True, writer=_failing_writer)
    state = make_state()
    m.save(1, state)
    m._save_thread.join()
    m._writer = np.savez  # the disk came back
    with pytest.raises(CheckpointSaveError):
        m.save(2, state)  # surfaces step 1's failure...
    m.save(2, state)  # ...and the retry goes through
    m.wait()
    assert m.latest_step() == 2
    assert_tree_equal(state, m.restore(state, 2))


def test_sync_save_failure_raises_inline(tmp_path):
    """Synchronous saves keep raising at the call site, not via wait()."""
    m = CheckpointManager(str(tmp_path), writer=_failing_writer)
    with pytest.raises(OSError, match="disk full"):
        m.save(1, make_state())


# ------------------------------------------------------------ snapshot store


def test_snapshot_roundtrip_bit_exact_including_bf16(tmp_path):
    store = SnapshotStore(str(tmp_path))
    state = make_state()
    digest = store.put(state)
    assert store.contains(digest)
    restored = store.restore(digest, state)
    assert_tree_equal(state, restored)
    # content address is a function of the bytes: restored re-hashes to it
    assert snapshot_digest(jax.tree.map(np.asarray, restored)) == digest


def test_snapshot_restore_into_structs(tmp_path):
    """Resurrect path: the parked spec keeps only ShapeDtypeStructs."""
    store = SnapshotStore(str(tmp_path))
    state = make_state()
    digest = store.put(state)
    like = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)
    assert_tree_equal(state, store.restore(digest, like))


def test_snapshot_put_dedups_identical_content(tmp_path):
    store = SnapshotStore(str(tmp_path))
    d1 = store.put(make_state(seed=3))
    d2 = store.put(make_state(seed=3))  # same bytes, fresh tree
    assert d1 == d2
    assert store.stats()["puts"] == 1
    assert store.stats()["dedup_hits"] == 1
    assert store.stats()["entries"] == 1


def test_snapshot_concurrent_puts_of_one_tree_publish_a_whole_snapshot(tmp_path):
    """Two parks of the same weights (the reconciler's idle tick racing an
    explicit scale_to_zero) put one content address at the same time. Each
    writer stages its own directory, so neither deletes the other's files
    and the published snapshot is always whole."""
    tree = {"w": np.arange(64 * 64, dtype=np.float32).reshape(64, 64),
            "b": np.ones(64, np.float32)}
    for rnd in range(40):
        store = SnapshotStore(str(tmp_path / f"r{rnd}"))
        barrier = threading.Barrier(6)
        digests, errors = [], []

        def put():
            try:
                barrier.wait(timeout=10)
                digests.append(store.put(tree))
            except Exception as e:  # noqa: BLE001 - reported by the assert below
                errors.append(e)

        threads = [threading.Thread(target=put) for _ in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads)
        assert errors == [] and len(digests) == 6 and len(set(digests)) == 1
        restored = store.restore(digests[0], tree)
        for k in tree:
            np.testing.assert_array_equal(np.asarray(restored[k]), tree[k])
        assert [d for d in os.listdir(store.directory) if d.endswith(".tmp")] == []


def test_snapshot_distinct_content_distinct_digests(tmp_path):
    store = SnapshotStore(str(tmp_path))
    assert store.put(make_state(seed=0)) != store.put(make_state(seed=1))
    assert store.stats()["entries"] == 2


def test_snapshot_retention_evicts_lru(tmp_path):
    store = SnapshotStore(str(tmp_path), retain=2)
    digests = [store.put(make_state(seed=s)) for s in range(4)]
    # os.utime granularity can tie mtimes on fast filesystems; eviction keeps
    # exactly `retain` entries either way
    assert store.stats()["entries"] == 2
    assert store.stats()["evicted"] == 2
    assert store.contains(digests[-1])


def test_snapshot_corruption_detected(tmp_path):
    store = SnapshotStore(str(tmp_path))
    state = make_state()
    digest = store.put(state)
    # flip bytes in one stored leaf
    leaf = os.path.join(store.path_of(digest), "leaf_00000.npy")
    raw = bytearray(open(leaf, "rb").read())
    raw[-4] ^= 0xFF
    open(leaf, "wb").write(bytes(raw))
    with pytest.raises(SnapshotIntegrityError):
        store.restore(digest, state)
    # verify=False is the caller's explicit opt-out
    store.restore(digest, state, verify=False)


def test_snapshot_missing_digest_raises(tmp_path):
    store = SnapshotStore(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        store.restore("0" * 32, make_state())
