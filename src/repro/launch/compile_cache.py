"""Warm-provisioning level 1: executable index + persistent XLA compile cache.

Two layers keep a rebuilt execution unit from paying XLA again:

1. ``EXECUTABLE_INDEX`` (in-process) — a content-addressed map from an
   *executable key* to an already-compiled program.  ``jax.jit`` keys its
   own cache by function identity, and every ``FunctionInstance`` rebuild
   creates fresh closures, so the merge→split→re-merge churn loop recompiles
   programs it was serving seconds earlier.  The index keys by *behavior*
   instead: a digest of every member spec's bytecode, closure values and
   defaults, the parameter/argument tree structure, the shape bucket, and
   the environment (jax version, backend, kernel dispatch mode).  A rebuilt
   unit whose key matches reuses the live executable — zero recompiles.
2. JAX's persistent compilation cache (cross-process) —
   ``enable_persistent_cache`` points jax at an on-disk cache directory
   (``$JAX_COMPILATION_CACHE_DIR``, else a fixed directory in the checkout)
   so even a fresh process (deploy, CI run, resurrect after restart)
   restores serialized executables instead of re-running XLA.

Safety invariants:

- Params are *passed as arguments* at call time (``compiled(params, *args)``),
  so two instances may share an executable while holding different weights;
  only the tree structure/dtypes enter the key.
- Effectful programs (``ctx.call_async`` lowers to an ``io_callback`` whose
  host callback closes over the owning platform) are NEVER inserted, so an
  index hit always yields a pure, platform-agnostic program.  Callers may
  therefore look up *before* tracing.
- Closure cells are digested by VALUE: two stages built from the same
  factory (same code object, different captured routing keys) get distinct
  keys.
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
import os
import threading
import types
import weakref
from pathlib import Path
from typing import Any, Mapping

import jax
import numpy as np

_MAX_ARRAY_BYTES = 1 << 20  # full-hash cap; larger arrays are sample-hashed
_MAX_DEPTH = 8


#: Where the persistent cache lives when ``JAX_COMPILATION_CACHE_DIR`` is
#: unset: one fixed directory in the checkout (listed in ``.gitignore``). The
#: path is part of JAX's cache key, so it must not move between runs.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_compile_cache")


def cache_dir() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_CACHE_DIR`."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_CACHE_DIR


def enable_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache at :func:`cache_dir`, with
    thresholds zeroed so every program is cached. A set
    ``JAX_COMPILATION_CACHE_DIR`` is read by JAX itself, so no directory is
    set in code then. Returns the directory."""
    directory = cache_dir()
    os.makedirs(directory, exist_ok=True)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_enable_compilation_cache", True)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory


def disable_persistent_cache() -> None:
    """Turn JAX's persistent cache off for the rest of the process, whatever
    the environment says — for measurements that must compile cold."""
    from jax.experimental.compilation_cache import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()


def environment_key() -> tuple:
    """Everything outside the spec that changes what a program lowers to.

    ``dispatch_mode`` matters because ``kernels/ops.py`` picks Pallas vs the
    jnp oracle per backend: flipping ``REPRO_USE_PALLAS`` mid-process must
    miss the index rather than reuse a stale lowering."""
    from repro.kernels import ops

    return (
        jax.__version__,
        jax.default_backend(),
        ops.dispatch_mode(),
        bool(jax.config.jax_enable_x64),
    )


def _digest_code(h, code: types.CodeType) -> None:
    h.update(code.co_code)
    h.update(repr(code.co_names).encode())
    h.update(repr(code.co_varnames).encode())
    h.update(repr(code.co_freevars).encode())
    for const in code.co_consts:
        if isinstance(const, types.CodeType):
            _digest_code(h, const)  # nested lambdas / comprehensions
        else:
            h.update(repr(const).encode())


def _digest_update(h, obj: Any, seen: set[int], depth: int = 0) -> None:
    if depth > _MAX_DEPTH:
        h.update(b"<deep>")
        return
    if obj is None or isinstance(obj, (bool, int, float, complex, str, bytes)):
        h.update(repr(obj).encode())
        return
    oid = id(obj)
    if oid in seen:
        h.update(b"<cycle>")
        return
    seen.add(oid)
    code = getattr(obj, "__code__", None)
    if code is not None:
        _digest_code(h, code)
        for cell in getattr(obj, "__closure__", None) or ():
            try:
                val = cell.cell_contents
            except ValueError:
                val = "<empty-cell>"
            _digest_update(h, val, seen, depth + 1)
        _digest_update(h, getattr(obj, "__defaults__", None), seen, depth + 1)
        kwdefaults = getattr(obj, "__kwdefaults__", None)
        for k in sorted(kwdefaults or ()):
            h.update(k.encode())
            _digest_update(h, kwdefaults[k], seen, depth + 1)
        return
    shape = getattr(obj, "shape", None)
    dtype = getattr(obj, "dtype", None)
    if shape is not None and dtype is not None:
        h.update(f"arr:{dtype}:{shape}".encode())
        try:
            arr = np.asarray(obj)
        except Exception:
            h.update(b"<opaque-array>")
            return
        if arr.nbytes <= _MAX_ARRAY_BYTES:
            h.update(np.ascontiguousarray(arr).tobytes())
        else:
            flat = arr.reshape(-1)
            idx = np.linspace(0, flat.shape[0] - 1, num=1024).astype(np.int64)
            h.update(np.ascontiguousarray(flat[idx]).tobytes())
        return
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        h.update(type(obj).__name__.encode())
        for f in dataclasses.fields(obj):
            h.update(f.name.encode())
            _digest_update(h, getattr(obj, f.name), seen, depth + 1)
        return
    if isinstance(obj, dict):
        h.update(b"dict")
        try:
            keys = sorted(obj)
        except TypeError:
            keys = list(obj)
        for k in keys:
            h.update(repr(k).encode())
            _digest_update(h, obj[k], seen, depth + 1)
        return
    if isinstance(obj, (list, tuple)):
        h.update(type(obj).__name__.encode())
        for item in obj:
            _digest_update(h, item, seen, depth + 1)
        return
    if isinstance(obj, (set, frozenset)):
        h.update(type(obj).__name__.encode())
        for item in sorted(obj, key=repr):
            _digest_update(h, item, seen, depth + 1)
        return
    if isinstance(obj, types.ModuleType):
        h.update(f"mod:{obj.__name__}".encode())
        return
    # Fallback: repr.  Default reprs embed the object address, so two
    # *distinct* unknown objects never collide (conservatively unequal);
    # value-repr'd objects (np dtypes, enums, paths) compare by content.
    h.update(repr(obj).encode())


# spec digests are memoized by object identity — FunctionSpec is frozen, and
# the weakref finalizer evicts the id when the spec is collected so a reused
# address can't alias a dead spec's digest
_SPEC_DIGESTS: dict[int, str] = {}
_SPEC_LOCK = threading.Lock()


def _evict_spec(key: int) -> None:
    with _SPEC_LOCK:
        _SPEC_DIGESTS.pop(key, None)


def spec_digest(spec) -> str:
    """Content digest of a FunctionSpec's *behavior*: name, trust domain,
    and the full fn closure tree.  Params are excluded — they are call-time
    arguments, and their structure enters the executable key separately."""
    key = id(spec)
    with _SPEC_LOCK:
        got = _SPEC_DIGESTS.get(key)
    if got is not None:
        return got
    h = hashlib.blake2b(digest_size=16)
    h.update(spec.name.encode())
    h.update(spec.trust_domain.encode())
    _digest_update(h, spec.fn, set())
    digest = h.hexdigest()
    with _SPEC_LOCK:
        _SPEC_DIGESTS[key] = digest
    weakref.finalize(spec, _evict_spec, key)
    return digest


def members_digest(specs: Mapping[str, Any]) -> str:
    """Digest of a whole execution unit.  ``TraceContext.call`` inlines
    co-located members into one program, so the key must cover EVERY member's
    spec, not just the entry's."""
    h = hashlib.blake2b(digest_size=16)
    for name in sorted(specs):
        h.update(name.encode())
        h.update(spec_digest(specs[name]).encode())
    return h.hexdigest()


class ExecutableIndex:
    """Process-wide LRU of compiled programs keyed by executable key.

    Entries are ``CompiledEntry`` values from ``core/function.py`` (held
    opaquely — only ``compile_s`` is read, for the saved-seconds counter).
    Only effect-free programs are ever inserted (see module docstring), so a
    hit is always safe to share across instances and platforms."""

    GUARDED_FIELDS = {
        "_entries": "_lock",
        "_hits": "_lock",
        "_misses": "_lock",
        "_inserts": "_lock",
        "_evictions": "_lock",
        "_saved_s": "_lock",
    }

    def __init__(self, max_entries: int = 512):
        self.max_entries = max_entries
        self._lock = threading.Lock()
        self._entries: collections.OrderedDict = collections.OrderedDict()
        self._hits = 0
        self._misses = 0
        self._inserts = 0
        self._evictions = 0
        self._saved_s = 0.0

    def lookup(self, key) -> Any | None:
        if key is None:
            return None
        with self._lock:
            entry = self._entries.get(key)
            if entry is None:
                self._misses += 1
                return None
            self._entries.move_to_end(key)
            self._hits += 1
            self._saved_s += float(getattr(entry, "compile_s", 0.0))
            return entry

    def insert(self, key, entry) -> None:
        if key is None:
            return
        with self._lock:
            if key in self._entries:
                self._entries.move_to_end(key)
                self._entries[key] = entry
                return
            self._entries[key] = entry
            self._inserts += 1
            while len(self._entries) > self.max_entries:
                self._entries.popitem(last=False)
                self._evictions += 1

    def clear(self) -> None:
        """Drop entries AND counters — used by the coldstart benchmark so a
        retried attempt measures a genuinely cold first cycle."""
        with self._lock:
            self._entries.clear()
            self._hits = 0
            self._misses = 0
            self._inserts = 0
            self._evictions = 0
            self._saved_s = 0.0

    def stats(self) -> dict:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self._hits,
                "misses": self._misses,
                "inserts": self._inserts,
                "evictions": self._evictions,
                "saved_s": round(self._saved_s, 4),
            }


EXECUTABLE_INDEX = ExecutableIndex()
