"""Step-atomic checkpointing with retention, async save, and
restore-with-reshard (elastic scaling).

Atomicity: a checkpoint is written to ``step_<N>.tmp/`` and ``os.rename``d
into place — a crash mid-save can never produce a readable-but-corrupt
checkpoint, so restart always finds a consistent latest step.

Elasticity: checkpoints store *logical* content (flattened arrays keyed by
tree path), not device layouts. ``restore`` re-shards every leaf onto the
mesh it is given — save on mesh A, restore on mesh B (tested), which is how
the framework handles node loss / cluster resize: restart with a new mesh
and continue from the latest step.

Multi-host note: on a real pod each process would write only its addressable
shards (same layout, per-process files) and restore with
``jax.make_array_from_single_device_arrays``; the single-process container
writes full arrays. The API is identical either way.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
import threading
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from repro.scheduler.clock import SYSTEM_CLOCK

# numpy can't serialize ml_dtypes types; store them as same-width uint views
# and record the true dtype in meta.json.
_VIEW_AS = {
    np.dtype(ml_dtypes.bfloat16): np.uint16,
    np.dtype(ml_dtypes.float8_e4m3fn): np.uint8,
    np.dtype(ml_dtypes.float8_e5m2): np.uint8,
}
_VIEW_BACK = {str(k): k for k in _VIEW_AS}


def _flatten_with_paths(tree):
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = {}
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out[key] = leaf
    return out


class CheckpointSaveError(RuntimeError):
    """An async save worker failed. Raised on the NEXT ``wait()`` /
    ``latest_step()`` / ``save()`` — the thread itself can only die silently,
    and a training loop that keeps stepping against a checkpointer that
    stopped persisting is the failure mode this surfaces."""


class CheckpointManager:
    def __init__(self, directory: str, *, retain: int = 3, async_save: bool = False,
                 clock=None, writer=None):
        self.directory = directory
        self.retain = retain
        self.async_save = async_save
        self.clock = clock if clock is not None else SYSTEM_CLOCK
        os.makedirs(directory, exist_ok=True)
        self._save_thread: threading.Thread | None = None
        self._save_error: BaseException | None = None
        self._writer = writer if writer is not None else np.savez
        self.save_log: list[dict] = []

    # --------------------------------------------------------------- save

    def save(self, step: int, state) -> None:
        if self.async_save:
            host_state = jax.tree.map(lambda x: np.asarray(x), state)  # snapshot
            self.wait()  # one in-flight save at a time; surfaces a prior failure
            self._save_thread = threading.Thread(
                target=self._save_guarded, args=(step, host_state), daemon=True
            )
            self._save_thread.start()
        else:
            self._save_sync(step, state)

    def _save_guarded(self, step: int, state) -> None:
        try:
            self._save_sync(step, state)
        except BaseException as exc:  # noqa: BLE001 — captured, re-raised on wait()
            self._save_error = exc

    def _surface_save_error(self) -> None:
        exc = self._save_error
        if exc is not None:
            # surfaced once: the failed step is gone either way, and the next
            # save may succeed (transient disk pressure, fixed permissions)
            self._save_error = None
            raise CheckpointSaveError(f"async checkpoint save failed: {exc!r}") from exc

    def wait(self) -> None:
        if self._save_thread is not None:
            self._save_thread.join()
            self._save_thread = None
        self._surface_save_error()

    def _save_sync(self, step: int, state) -> None:
        t0 = time.perf_counter()
        tmp = os.path.join(self.directory, f"step_{step:010d}.tmp")
        final = os.path.join(self.directory, f"step_{step:010d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        flat = _flatten_with_paths(state)
        arrays = {k: np.asarray(v) for k, v in flat.items()}
        meta = {
            "step": step,
            "keys": sorted(arrays),
            "dtypes": {k: str(v.dtype) for k, v in arrays.items()},
            "shapes": {k: list(v.shape) for k, v in arrays.items()},
            "wall_time": self.clock.now(),
        }
        arrays = {
            k: (v.view(_VIEW_AS[v.dtype]) if v.dtype in _VIEW_AS else v)
            for k, v in arrays.items()
        }
        self._writer(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)  # atomic publish
        self._cleanup()
        self.save_log.append({"step": step, "seconds": time.perf_counter() - t0})

    def _cleanup(self) -> None:
        steps = self.all_steps()
        for s in steps[: -self.retain] if self.retain else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:010d}"), ignore_errors=True)

    # --------------------------------------------------------------- restore

    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    out.append(int(name.split("_")[1]))
                except ValueError:
                    continue
        return sorted(out)

    def latest_step(self) -> int | None:
        # A finished-but-failed async worker must not let the PREVIOUS step
        # silently masquerade as latest. Only a completed thread is joined —
        # latest_step never blocks behind an in-flight save.
        t = self._save_thread
        if t is not None and not t.is_alive():
            self.wait()
        else:
            self._surface_save_error()
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, like, step: int | None = None, *, mesh=None, rules=None, shardings=None):
        """Restore into the structure of ``like`` (a pytree of arrays or
        ShapeDtypeStructs). Re-shards onto ``shardings`` (a matching pytree)
        or onto each ``like`` leaf's own sharding if it has one."""
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, f"step_{step:010d}")
        data = dict(np.load(os.path.join(path, "arrays.npz")).items())
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
        for key, dt in meta["dtypes"].items():
            if dt in _VIEW_BACK and key in data:
                data[key] = data[key].view(_VIEW_BACK[dt])
        flat_like = _flatten_with_paths(like)
        leaves_like, treedef = jax.tree_util.tree_flatten(like)
        flat_shard = _flatten_with_paths(shardings) if shardings is not None else {}
        restored = {}
        for key, leaf in flat_like.items():
            arr = data[key]
            target_dtype = jnp.result_type(leaf)
            sharding = flat_shard.get(key)
            if sharding is None:
                sharding = getattr(leaf, "sharding", None)
                if sharding is not None and getattr(sharding, "is_fully_addressable", True) is False:
                    sharding = None
            val = jnp.asarray(arr, dtype=target_dtype)
            if sharding is not None:
                val = jax.device_put(val, sharding)
            restored[key] = val
        # rebuild in tree order
        keys_in_order = list(_flatten_with_paths(like).keys())
        return jax.tree_util.tree_unflatten(treedef, [restored[k] for k in keys_in_order])


# ------------------------------------------------------------------ snapshots


def snapshot_digest(tree) -> str:
    """Content address of a param tree: treedef plus every leaf's path,
    dtype, shape, and full bytes. Bit-exact by construction — two trees
    share a digest iff they restore identically."""
    flat = _flatten_with_paths(tree)
    treedef = jax.tree_util.tree_flatten(tree)[1]
    h = hashlib.blake2b(digest_size=16)
    h.update(str(treedef).encode())
    for key in sorted(flat):
        arr = np.asarray(flat[key])
        h.update(key.encode())
        h.update(str(arr.dtype).encode())
        h.update(repr(arr.shape).encode())
        h.update(arr.tobytes())
    return h.hexdigest()


class SnapshotIntegrityError(RuntimeError):
    """Restored bytes do not re-hash to the requested digest (on-disk
    corruption / truncation) — the caller must fall back to a cold build."""


class SnapshotStore:
    """Content-addressed instance snapshots — warm-provisioning level 2.

    Layout: ``<dir>/<digest>/leaf_00000.npy .. leaf_NNNNN.npy + meta.json``
    where the digest is :func:`snapshot_digest` of the param tree. Writes go
    to a ``<digest>.<unique>.tmp`` directory of the writer's own and
    ``os.rename`` into place (same crash-atomicity as checkpoints; when two
    writers race on one digest the first rename wins and the other's copy
    is dropped); ``put`` of an already-stored tree is a metadata touch
    (content-address dedup — a fleet of same-weights functions stores one
    copy). ``restore`` opens each leaf with ``np.load(mmap_mode='r')`` so
    bytes are paged in lazily, and by default re-hashes what it read against
    the digest — a resurrect either gets bit-exact params or an integrity
    error, never silent corruption.

    ``retain`` > 0 keeps only the N most-recently-used snapshots (mtime LRU;
    both put-dedup and restore refresh recency). 0 disables eviction — the
    platform pins parked functions' snapshots simply by not enabling it.
    """

    GUARDED_FIELDS = {
        "puts": "_lock",
        "dedup_hits": "_lock",
        "restores": "_lock",
        "put_s": "_lock",
        "restore_s": "_lock",
        "evicted": "_lock",
    }

    def __init__(self, directory: str, *, retain: int = 0, clock=None):
        self.directory = directory
        self.retain = retain
        self.clock = clock if clock is not None else SYSTEM_CLOCK
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self.puts = 0
        self.dedup_hits = 0
        self.restores = 0
        self.put_s = 0.0
        self.restore_s = 0.0
        self.evicted = 0

    def path_of(self, digest: str) -> str:
        return os.path.join(self.directory, digest)

    def contains(self, digest: str) -> bool:
        return os.path.isdir(self.path_of(digest))

    def put(self, tree) -> str:
        """Store ``tree`` under its content address; returns the digest."""
        t0 = time.perf_counter()
        digest = snapshot_digest(tree)
        final = self.path_of(digest)
        if os.path.isdir(final):
            os.utime(final)  # refresh LRU recency
            with self._lock:
                self.dedup_hits += 1
            return digest
        # a staging directory of this writer's own: a concurrent put of the
        # same content (two parks racing) must not delete files under it
        tmp = tempfile.mkdtemp(prefix=f"{digest}.", suffix=".tmp", dir=self.directory)
        flat = _flatten_with_paths(tree)
        keys = sorted(flat)
        treedef = jax.tree_util.tree_flatten(tree)[1]
        meta = {
            "digest": digest,
            "keys": keys,
            "treedef": str(treedef),
            "dtypes": {},
            "shapes": {},
            "wall_time": self.clock.now(),
        }
        for i, key in enumerate(keys):
            arr = np.asarray(flat[key])
            meta["dtypes"][key] = str(arr.dtype)
            meta["shapes"][key] = list(arr.shape)  # BEFORE ascontiguousarray: it promotes 0-d to (1,)
            arr = np.ascontiguousarray(arr)
            stored = arr.view(_VIEW_AS[arr.dtype]) if arr.dtype in _VIEW_AS else arr
            np.save(os.path.join(tmp, f"leaf_{i:05d}.npy"), stored)
        with open(os.path.join(tmp, "meta.json"), "w") as f:
            json.dump(meta, f)
        try:
            os.rename(tmp, final)  # atomic publish
        except OSError:
            if not os.path.isdir(final):
                raise
            shutil.rmtree(tmp)  # another writer published the same content first
        with self._lock:
            self.puts += 1
            self.put_s += time.perf_counter() - t0
        self._evict()
        return digest

    def restore(self, digest: str, like, *, verify: bool = True):
        """Rebuild the tree of ``like`` (arrays or ShapeDtypeStructs) from the
        snapshot at ``digest``. ``verify=True`` re-hashes the restored host
        bytes and raises :class:`SnapshotIntegrityError` on mismatch."""
        t0 = time.perf_counter()
        final = self.path_of(digest)
        if not os.path.isdir(final):
            raise FileNotFoundError(f"no snapshot {digest} in {self.directory}")
        os.utime(final)  # refresh LRU recency
        with open(os.path.join(final, "meta.json")) as f:
            meta = json.load(f)
        host = {}
        for i, key in enumerate(meta["keys"]):
            arr = np.load(os.path.join(final, f"leaf_{i:05d}.npy"), mmap_mode="r")
            dt = meta["dtypes"][key]
            if dt in _VIEW_BACK:
                arr = arr.view(_VIEW_BACK[dt])
            # a memmap is never 0-d: np.load promotes scalar leaves to (1,);
            # reshape restores the recorded shape without copying
            host[key] = arr.reshape(meta["shapes"][key])
        flat_like = _flatten_with_paths(like)
        leaves_like, treedef = jax.tree_util.tree_flatten(like)
        if verify:
            np_tree = jax.tree_util.tree_unflatten(
                treedef, [np.asarray(host[k]) for k in flat_like]
            )
            got = snapshot_digest(np_tree)
            if got != digest:
                raise SnapshotIntegrityError(
                    f"snapshot {digest} restored with digest {got}"
                )
        out = jax.tree_util.tree_unflatten(
            treedef,
            [jnp.asarray(host[k], dtype=jnp.result_type(flat_like[k])) for k in flat_like],
        )
        with self._lock:
            self.restores += 1
            self.restore_s += time.perf_counter() - t0
        return out

    def _evict(self) -> None:
        if not self.retain:
            return
        dirs = []
        for name in os.listdir(self.directory):
            path = os.path.join(self.directory, name)
            if name.endswith(".tmp") or not os.path.isdir(path):
                continue
            dirs.append((os.path.getmtime(path), path))
        dirs.sort()
        for _, path in dirs[: -self.retain]:
            shutil.rmtree(path, ignore_errors=True)
            with self._lock:
                self.evicted += 1

    def stats(self) -> dict:
        with self._lock:
            out = {
                "puts": self.puts,
                "dedup_hits": self.dedup_hits,
                "restores": self.restores,
                "put_s": round(self.put_s, 4),
                "restore_s": round(self.restore_s, 4),
                "evicted": self.evicted,
            }
        out["entries"] = sum(
            1 for d in os.listdir(self.directory) if not d.endswith(".tmp")
        )
        return out
