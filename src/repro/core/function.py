"""FaaS functions and their serving instances.

A :class:`FunctionSpec` is the *bring-your-own-function-code* unit: a pure
JAX-traceable callable ``fn(ctx, params, *args)`` whose only impurity is
calling other functions through the platform context (``ctx.call`` /
``ctx.call_async``).

A :class:`FunctionInstance` is the running analogue of a FaaS container: it
hosts one or more functions' code + weights. Entries whose trace is
*self-contained* (leaf functions; fused groups whose calls all resolve to
co-located members) are served as ONE compiled XLA program. Entries with a
synchronous boundary call run as *interpreter glue* (EagerContext): the
function's code executes in the host runtime and each outbound call is a
real blocking dispatch through the platform — the blocking-socket analogue
the Function Handler observes. Fusion turns glued chains into compiled
units; the payoff is real compiler-level cross-function optimization, not
simulation.
"""
from __future__ import annotations

import dataclasses
import enum
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import InvocationError


@dataclasses.dataclass(frozen=True)
class FunctionSpec:
    name: str
    fn: Callable  # fn(ctx, params, *args) -> pytree
    params: Any = None
    trust_domain: str = "default"
    description: str = ""


# Per-instance runtime footprint (container language runtime + loaded libs).
# A FaaS instance is a container; tinyFaaS/K8s Python containers idle at
# ~30-60 MiB RSS, and the paper's RAM savings come precisely from retiring
# these duplicated runtimes. Our in-process instances share one interpreter,
# so the platform's RAM metric models this per-container constant explicitly
# (documented in EXPERIMENTS.md §Paper-fidelity); buffer accounting
# (weights + compiled workspace) is measured, not modeled.
INSTANCE_RUNTIME_OVERHEAD_BYTES = 32 * 2**20


def tree_bytes(tree) -> int:
    total = 0
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "nbytes"):
            total += int(leaf.nbytes)
        elif hasattr(leaf, "size") and hasattr(leaf, "dtype"):
            total += int(leaf.size) * jnp.dtype(leaf.dtype).itemsize
    return total


#: XLA options of every program the platform compiles. Without excess
#: precision a bfloat16 value is rounded where the code rounds it, whatever
#: the compiler fuses around it, so a fused unit computes the bits its
#: members compute glued. With it, a fusion may keep such a value in
#: float32: on a TPU v5e the fused Mellum2 chain's K then differed from the
#: glued stage's in the last bit, a MoE router split near ties the other
#: way, and the merge health check refused the chain.
COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def _structs_of(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(jnp.shape(a), jnp.result_type(a)), tree)


def _struct_key(tree) -> tuple:
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return (str(treedef), tuple((tuple(l.shape), str(l.dtype)) for l in leaves))


class InstanceState(enum.Enum):
    """Control-plane lifecycle: PROVISIONING (being built/compiled) ->
    READY (health-checked, not yet routed) -> SERVING (routed) ->
    DRAINING (displaced, in-flight requests finishing) -> RETIRED (drained,
    memory freed). Transitions are driven by the ControlPlane's epoch
    publishes; see repro.core.lifecycle."""

    PROVISIONING = "provisioning"
    READY = "ready"
    SERVING = "serving"
    DRAINING = "draining"
    RETIRED = "retired"


@dataclasses.dataclass
class CompiledEntry:
    compiled: Any
    temp_bytes: int
    code_bytes: int
    output_bytes: int
    compile_s: float


class BatchingUnsupported(Exception):
    """Entry cannot run as one batched program (e.g. host-callback effects)."""


def _finalize_compiled(compiled, t0: float) -> CompiledEntry:
    """Package a compiled executable with its memory-analysis footprint."""
    temp = code = out = 0
    try:
        ma = compiled.memory_analysis()
        temp = int(getattr(ma, "temp_size_in_bytes", 0) or 0)
        code = int(getattr(ma, "generated_code_size_in_bytes", 0) or 0)
        out = int(getattr(ma, "output_size_in_bytes", 0) or 0)
    except Exception:  # pragma: no cover - backend without memory analysis
        pass
    return CompiledEntry(compiled, temp, code, out, time.perf_counter() - t0)


def _footprint_bytes(params, compiled: dict) -> int:
    """One instance's live footprint: container runtime constant + weights +
    compiled-program workspace/code/output buffers. Shared by the live
    `resident_bytes` metric and `retire`'s freed-bytes accounting so the RAM
    the control plane reports freed is exactly the RAM it was counting."""
    total = INSTANCE_RUNTIME_OVERHEAD_BYTES + tree_bytes(params)
    for ce in compiled.values():
        total += ce.temp_bytes + ce.code_bytes + ce.output_bytes
    return total


class FunctionInstance:
    """One running execution unit hosting >= 1 functions ("members")."""

    _counter = 0
    _counter_lock = threading.Lock()

    GUARDED_FIELDS = {
        "cache_hits": "_lock",
        "cache_misses": "_lock",
        "compile_wall_s": "_lock",
    }

    def __init__(self, specs: dict[str, FunctionSpec], platform):
        with FunctionInstance._counter_lock:
            FunctionInstance._counter += 1
            seq = FunctionInstance._counter
        self.members: dict[str, FunctionSpec] = dict(specs)
        self.instance_id = f"inst{seq}[{'+'.join(sorted(specs))}]"
        self.platform = platform
        self.params: dict[str, Any] = {n: s.params for n, s in specs.items()}
        self.state = InstanceState.PROVISIONING
        self._compiled: dict[tuple, CompiledEntry] = {}
        self._eager_entries: set[tuple] = set()
        self._batch_unsupported: set[tuple] = set()
        self._lock = threading.Lock()
        self._active = 0
        self._idle_event = threading.Event()
        self._idle_event.set()
        self.created_at = time.perf_counter()
        # provisioning profile: executable-index hits vs real XLA compiles
        self.cache_hits = 0
        self.cache_misses = 0
        self.compile_wall_s = 0.0
        # Content digest of every member's behavior (TraceContext.call inlines
        # co-located members, so the compiled program depends on ALL of them)
        # plus the param-tree structure. None disables executable sharing for
        # this instance — indexing is an optimization, never a requirement.
        try:
            from repro.launch.compile_cache import members_digest

            self._members_digest = members_digest(self.members)
            self._params_skey = _struct_key(self.params)
        except Exception:  # pragma: no cover - undigestable spec
            self._members_digest = None
            self._params_skey = None

    # ----------------------------------------------------------- lifecycle

    def mark_ready(self):
        self.state = InstanceState.READY

    def mark_serving(self):
        """Routed by an epoch publish (called under the routing lock)."""
        if self.state != InstanceState.RETIRED:
            self.state = InstanceState.SERVING

    def begin_drain(self):
        """Displaced by an epoch publish (called under the routing lock, in
        the same critical section that removed this instance's last route)."""
        with self._lock:
            if self.state != InstanceState.RETIRED:
                self.state = InstanceState.DRAINING

    def begin_request(self):
        with self._lock:
            if self.state == InstanceState.RETIRED:
                raise InvocationError(f"{self.instance_id} is {self.state.value}")
            self._active += 1
            self._idle_event.clear()

    def end_request(self):
        with self._lock:
            self._active -= 1
            if self._active == 0:
                self._idle_event.set()

    def outstanding(self) -> int:
        """In-flight request count (begin/end_request bracketing) — the
        least-outstanding spread's load signal. Pod work queued behind a
        busy orchestrated worker but not yet begun is not counted."""
        with self._lock:
            return self._active

    def retire(self, timeout: float = 30.0) -> int:
        """Drain in-flight requests, terminate, free weights. Returns bytes
        freed (the RAM the fusion reclaims).

        The RETIRED flip and the in-flight check share the instance lock, so
        a request that slipped past resolution cannot begin AFTER the params
        are freed: either it begins while DRAINING (and retire keeps
        waiting), or it finds RETIRED and raises InvocationError into the
        platform's re-resolve retry path."""
        self.begin_drain()
        if self.state == InstanceState.RETIRED:
            return 0  # idempotent: already drained and freed
        deadline = time.perf_counter() + timeout
        while True:
            with self._lock:
                if self._active == 0 or time.perf_counter() >= deadline:
                    self.state = InstanceState.RETIRED
                    params, compiled = self.params, self._compiled
                    self.params = {}
                    self._compiled = {}
                    break
            self._idle_event.wait(max(0.0, deadline - time.perf_counter()))
        return _footprint_bytes(params, compiled)

    # ----------------------------------------------------------- compile

    def _entry_callable(self, entry: str):
        from repro.core.context import TraceContext

        spec = self.members[entry]

        def run(params_by_member, *args):
            ctx = TraceContext(self.platform, self, params_by_member, entry)
            return spec.fn(ctx, params_by_member[entry], *args)

        return run

    def _executable_key(self, kind: str, entry: str, skey: tuple, bucket: int | None = None):
        """Process-wide executable-index key, or None when indexing is off."""
        if self._members_digest is None:
            return None
        from repro.launch.compile_cache import environment_key

        return (kind, entry, self._members_digest, self._params_skey, skey,
                bucket, environment_key())

    def _note_compile(self, *, hit: bool, seconds: float, saved_s: float = 0.0) -> None:
        note = getattr(self.platform, "note_compile", None)
        if note is not None:
            note(hit=hit, seconds=seconds, saved_s=saved_s)

    def get_compiled(self, entry: str, args: tuple) -> CompiledEntry | None:
        """Compiled program for this entry, or None when the entry crosses an
        instance boundary synchronously (-> interpreter-glue execution)."""
        key = (entry, _struct_key(args))
        with self._lock:
            if key in self._eager_entries:
                return None
            got = self._compiled.get(key)
        if got is not None:
            return got
        from repro.core.context import BoundaryCall
        from repro.launch.compile_cache import EXECUTABLE_INDEX

        t0 = time.perf_counter()
        # Index lookup happens BEFORE tracing: the key doesn't depend on the
        # trace, and only effect-free programs are ever inserted, so a hit is
        # always a pure program safe to share across instances/platforms.
        xkey = self._executable_key("single", entry, key[1])
        cached = EXECUTABLE_INDEX.lookup(xkey)
        if cached is not None:
            entry_obj = dataclasses.replace(cached, compile_s=time.perf_counter() - t0)
            with self._lock:
                self._compiled[key] = entry_obj
                self.cache_hits += 1
            self._note_compile(hit=True, seconds=entry_obj.compile_s,
                               saved_s=cached.compile_s)
            return entry_obj
        run = self._entry_callable(entry)
        params_structs = _structs_of(self.params)
        arg_structs = _structs_of(args)
        try:
            traced = jax.jit(run).trace(params_structs, *arg_structs)
            compiled = traced.lower().compile(COMPILER_OPTIONS)
        except BoundaryCall:
            with self._lock:
                self._eager_entries.add(key)
            return None
        entry_obj = _finalize_compiled(compiled, t0)
        with self._lock:
            self._compiled[key] = entry_obj
            self.cache_misses += 1
            self.compile_wall_s += entry_obj.compile_s
        # Effectful programs (ctx.call_async -> io_callback closing over THIS
        # platform) must stay private to this instance; sharing one would
        # route another platform's async calls through a dead dispatcher.
        if not traced.jaxpr.effects:
            EXECUTABLE_INDEX.insert(xkey, entry_obj)
        self._note_compile(hit=False, seconds=entry_obj.compile_s)
        return entry_obj

    # ----------------------------------------------------------- execute

    def execute(self, entry: str, args: tuple):
        """Run one request to completion (synchronous, device-synced)."""
        ce = self.get_compiled(entry, args)
        if ce is None:  # interpreter glue: host-dispatched outbound calls
            from repro.core.context import EagerContext

            spec = self.members[entry]
            ctx = EagerContext(self.platform, self, self.params, entry)
            out = spec.fn(ctx, self.params[entry], *args)
        else:
            out = ce.compiled(self.params, *args)
        jax.block_until_ready(out)
        return out

    # ----------------------------------------------------------- batched execute

    def _get_batched(self, entry: str, args: tuple, bucket: int) -> CompiledEntry | None:
        """Compiled program serving ``bucket`` requests of this entry at once,
        or None when the entry cannot be a single program (boundary calls,
        unbatchable effects).

        The program takes the k request pytrees SEPARATELY, stacks them along
        a new leading axis inside the trace, vmaps the entry over it, and
        slices the outputs back apart — so gather/scatter of the batch is
        XLA-fused with the compute and the host pays ONE dispatch per batch
        (per-leaf host-side stack/split was measured at ~10x the cost of the
        batched execution itself)."""
        key = ("__batch__", entry, _struct_key(args), bucket)
        with self._lock:
            if key in self._batch_unsupported:
                return None
            got = self._compiled.get(key)
        if got is not None:
            return got
        from repro.launch.compile_cache import EXECUTABLE_INDEX
        from repro.scheduler.batching import split_results, stack_requests

        t0 = time.perf_counter()
        xkey = self._executable_key("batch", entry, key[2], bucket)
        cached = EXECUTABLE_INDEX.lookup(xkey)
        if cached is not None:
            entry_obj = dataclasses.replace(cached, compile_s=time.perf_counter() - t0)
            with self._lock:
                self._compiled[key] = entry_obj
                self.cache_hits += 1
            self._note_compile(hit=True, seconds=entry_obj.compile_s,
                               saved_s=cached.compile_s)
            return entry_obj
        run = self._entry_callable(entry)

        def batched_run(params, *requests):
            stacked = stack_requests(list(requests))
            outs = jax.vmap(run, in_axes=(None,) + (0,) * len(stacked))(params, *stacked)
            return tuple(split_results(outs, len(requests)))

        params_structs = _structs_of(self.params)
        arg_structs = _structs_of(args)
        try:
            # One trace serves both the effects check and the lowering —
            # tracing a model-sized entry twice would double the compile
            # stall the bucket-reuse logic exists to avoid.
            traced = jax.jit(batched_run).trace(params_structs, *([arg_structs] * bucket))
            # Effectful entries (ctx.call_async -> io_callback) must NOT
            # batch: the callback fires once per vmap lane, so bucket padding
            # would replay the last request's side effects per padded lane.
            if traced.jaxpr.effects:
                raise BatchingUnsupported(entry)
            compiled = traced.lower().compile(COMPILER_OPTIONS)
        except Exception:  # noqa: BLE001 — includes BoundaryCall. Batching is an
            # optimization: anything vmap/XLA rejects (boundary dispatch, host
            # callbacks, effects) falls back to per-request execution, never
            # to a request failure.
            with self._lock:
                self._batch_unsupported.add(key)
            return None
        entry_obj = _finalize_compiled(compiled, t0)
        with self._lock:
            self._compiled[key] = entry_obj
            self.cache_misses += 1
            self.compile_wall_s += entry_obj.compile_s
        # Reaching here implies traced.jaxpr.effects was empty (effectful
        # entries raised BatchingUnsupported above) — safe to share.
        EXECUTABLE_INDEX.insert(xkey, entry_obj)
        self._note_compile(hit=False, seconds=entry_obj.compile_s)
        return entry_obj

    def execute_batch(self, entry: str, args_list: list[tuple], max_bucket: int | None = None) -> list:
        """Run k compatible requests as ONE execution where possible.

        Requests stack along a new leading axis, padded up to a power-of-two
        bucket (capped at ``max_bucket``, normally the scheduler's max_batch,
        so a full batch never pads past its configured size) — at most
        O(log max_batch) batched programs ever compile. The batch axis is
        carried by vmap, so each request sees its original shapes. Entries
        that cannot compile as one program run per-request."""
        k = len(args_list)
        if k == 1:
            return [self.execute(entry, args_list[0])]
        from repro.scheduler.batching import next_batch_bucket

        skey = _struct_key(args_list[0])
        with self._lock:
            # Prefer an already-compiled bucket that fits (padding is nearly
            # free; a fresh XLA compile mid-traffic is a multi-second stall).
            fitting = [
                key[3] for key in self._compiled
                if len(key) == 4 and key[0] == "__batch__" and key[1] == entry
                and key[2] == skey and key[3] >= k
            ]
        bucket = min(fitting) if fitting else next_batch_bucket(k, max_bucket)
        if bucket < k:
            # Non-power-of-two max_bucket clamps below k (e.g. 6 requests,
            # cap 6 -> bucket 4): run power-of-two chunks instead of minting
            # a never-reused bucket-6 program.
            out: list = []
            for i in range(0, k, bucket):
                out.extend(self.execute_batch(entry, args_list[i : i + bucket], max_bucket))
            return out
        ce = self._get_batched(entry, args_list[0], bucket)
        if ce is None:
            return [self.execute(entry, a) for a in args_list]
        padded = args_list + [args_list[-1]] * (bucket - k)
        outs = ce.compiled(self.params, *padded)
        jax.block_until_ready(outs)
        return list(outs[:k])

    # ----------------------------------------------------------- metrics

    def provision_profile(self) -> dict:
        """How this instance's programs came to exist: executable-index hits
        vs real XLA compiles (and their wall seconds). A fully warm build has
        ``cache_misses == 0`` — the signal the provisioning stats use to
        classify a merge/split/resurrect as warm."""
        with self._lock:
            return {
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses,
                "compile_wall_s": round(self.compile_wall_s, 4),
            }

    def resident_bytes(self) -> int:
        """Live footprint of this execution unit: the container runtime
        constant + weights + compiled-program workspace (temp arena),
        generated code, and output staging buffers."""
        if self.state == InstanceState.RETIRED:
            return 0
        with self._lock:
            return _footprint_bytes(self.params, self._compiled)

    def __repr__(self):
        return f"<{self.instance_id} {self.state.value} members={sorted(self.members)}>"
