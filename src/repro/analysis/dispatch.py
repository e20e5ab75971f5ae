"""Dispatch-hygiene tracer: steady-state recompiles and host syncs.

Two hazards this repo has already shipped and hand-fixed once each:

* **Steady-state recompiles** — a decode loop whose batch/chunk shapes are
  not padded to a closed bucket set retraces and recompiles mid-stream
  (the PR 5 non-pow2 bucket bug). Compiles are observed via
  ``jax.monitoring``'s ``/jax/core/compile/backend_compile_duration``
  event, which fires exactly once per backend compile and never on cache
  hits, so ``delta(snapshot).compiles == 0`` is a precise "no new
  programs" assertion.

* **Per-token host syncs** — an eager ``int(...)``/``np.asarray(...)`` on
  a device array inside the token loop serializes every step on a
  device→host transfer (the PR 5 eager-argmax bug). JAX's transfer guard
  is a no-op on the CPU backend, so while armed the tracer patches
  ``numpy.asarray`` and ``jax.device_get`` and counts calls whose
  argument is a concrete ``jax.Array``. The smoke gate allows one batched
  fetch per decode step plus O(1) per request (seating/finishing) and
  fails on anything per-token-per-lane.

The tracer is a process-wide singleton (``TRACER``), disarmed by default
(zero overhead: arming is what installs the patches). ``load_bench
--serve --smoke`` arms it after warmup and asserts on the deltas;
``kernels/ops.py`` reports every op entry with the path it took (kernel,
interpret or ref), and eager entries separately (eager dispatch is
legitimate on the unfused interpreter path).

While armed it also sums the seconds spent in merge and split health checks
(``merge_check_s``, the durations of the merger's ``merge.check`` phases):
with compile time, the set-up cost a settled deployment has paid.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

from repro.analysis.guards import guarded_by


@dataclass(frozen=True)
class DispatchSnapshot:
    compiles: int
    host_syncs: int
    decode_steps: int
    kernel_calls: int
    compile_s: float = 0.0  # backend compile seconds
    merge_check_s: float = 0.0  # health-check seconds (merge.check phases)


class DispatchTracer:
    """Armable recompile + host-sync counter. See module docstring.

    Thread-safe and re-entrant: ``arm``/``disarm`` are ref-counted under
    ``_mu``, so overlapping measurement windows (the smoke gate arming
    while the overhead gate is already armed) never double-install the
    transfer patches — and never capture an installed wrapper as the
    "original" to restore, which would leak the patch forever."""

    GUARDED_FIELDS = {
        "_arm_count": "_mu",
        "_listener_installed": "_mu",
        "_unpatch": "_mu",
        "compiles": "_mu",
        "compile_s": "_mu",
        "merge_check_s": "_mu",
        "host_syncs": "_mu",
        "decode_steps": "_mu",
        "kernel_calls": "_mu",
        "paths": "_mu",
    }

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self._mu = threading.Lock()
        # lock-free fast-path flag the hot hooks read; written ONLY under
        # _mu. A stale read at an arm/disarm boundary misses/adds at most
        # one in-flight event — never a leak or a crash — so the hooks
        # stay O(1) with no lock acquisition while disarmed.
        self._armed = False
        self._arm_count = 0
        self._listener_installed = False
        self._unpatch = None  # installed patches' restore thunk, or None
        self.compiles = 0
        self.compile_s = 0.0
        self.merge_check_s = 0.0
        self.host_syncs = 0
        self.decode_steps = 0
        self.kernel_calls: dict[str, int] = {}
        self.paths: dict[tuple[str, str], int] = {}

    @property
    def armed(self) -> bool:
        return self._armed

    # -- wiring ------------------------------------------------------------

    def _on_event(self, event: str, duration: float, **kw) -> None:
        # jax.monitoring has no unregister API, so the listener outlives
        # every disarm: it must gate on the armed state or steady-state
        # compile asserts would see compiles from unrelated code between
        # measurement windows
        if event == self._EVENT and self._armed:
            with self._mu:
                if self._arm_count > 0:
                    self.compiles += 1
                    self.compile_s += duration

    @guarded_by("_mu")
    def _install_listener(self) -> None:
        if self._listener_installed:
            return
        import jax.monitoring
        # there is no unregister API; the listener stays and filters by event
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._listener_installed = True

    def _is_device_array(self, x) -> bool:
        import jax
        return isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer)

    @guarded_by("_mu")
    def _patch_transfers(self) -> None:
        """Install the asarray/device_get counting wrappers. Only called on
        the 0 -> 1 arm transition with the previous patches restored, so the
        captured originals are always the real functions."""
        if self._unpatch is not None:
            return
        import jax
        import numpy

        orig_asarray = numpy.asarray
        orig_device_get = jax.device_get
        tracer = self

        def asarray(a, *args, **kw):
            if tracer._armed and tracer._is_device_array(a):
                with tracer._mu:
                    if tracer._arm_count > 0:
                        tracer.host_syncs += 1
            return orig_asarray(a, *args, **kw)

        def device_get(x):
            if tracer._armed:
                with tracer._mu:
                    if tracer._arm_count > 0:
                        tracer.host_syncs += 1
            return orig_device_get(x)

        numpy.asarray = asarray
        jax.device_get = device_get
        self._unpatch = lambda: (
            setattr(numpy, "asarray", orig_asarray),
            setattr(jax, "device_get", orig_device_get),
        )

    # -- public API --------------------------------------------------------

    def arm(self) -> None:
        """Begin (or join) a measurement window. Every ``arm`` needs a
        matching ``disarm``; patches install on the first and are removed
        by the last, so mid-flight re-arms neither double-count nor leak."""
        with self._mu:
            self._arm_count += 1
            if self._arm_count == 1:
                self._install_listener()
                self._patch_transfers()
                self._armed = True

    def disarm(self) -> None:
        with self._mu:
            if self._arm_count == 0:
                return  # idempotent: stray disarms don't underflow
            self._arm_count -= 1
            if self._arm_count == 0:
                self._armed = False
                if self._unpatch is not None:
                    self._unpatch()
                    self._unpatch = None

    def note_decode_step(self) -> None:
        if self._armed:
            with self._mu:
                self.decode_steps += 1

    def note_merge_check(self, seconds: float) -> None:
        if self._armed:
            with self._mu:
                if self._arm_count > 0:
                    self.merge_check_s += seconds

    def note_kernel_call(self, name: str, probe=None, path: str | None = None) -> None:
        """An op entry and the ``path`` it dispatched to (``kernel`` |
        ``interpret`` | ``ref``). ``paths`` counts every entry, traced or
        eager, by (op, path) — what a smoke run reads to show the device
        path really ran. ``kernel_calls`` counts eager entries only
        (concrete operand): legitimate on the unfused interpreter path,
        recorded so reports show the eager/traced split."""
        if not self._armed:
            return
        if path is not None:
            with self._mu:
                self.paths[(name, path)] = self.paths.get((name, path), 0) + 1
        if probe is not None:
            try:
                if not self._is_device_array(probe):
                    return
            except Exception:
                return
        with self._mu:
            self.kernel_calls[name] = self.kernel_calls.get(name, 0) + 1

    def path_counts(self) -> dict[tuple[str, str], int]:
        """A copy of the (op, path) entry counts."""
        with self._mu:
            return dict(self.paths)

    def snapshot(self) -> DispatchSnapshot:
        with self._mu:
            return DispatchSnapshot(
                compiles=self.compiles,
                host_syncs=self.host_syncs,
                decode_steps=self.decode_steps,
                kernel_calls=sum(self.kernel_calls.values()),
                compile_s=self.compile_s,
                merge_check_s=self.merge_check_s,
            )

    def delta(self, since: DispatchSnapshot) -> DispatchSnapshot:
        now = self.snapshot()
        return DispatchSnapshot(
            compiles=now.compiles - since.compiles,
            host_syncs=now.host_syncs - since.host_syncs,
            decode_steps=now.decode_steps - since.decode_steps,
            kernel_calls=now.kernel_calls - since.kernel_calls,
            compile_s=now.compile_s - since.compile_s,
            merge_check_s=now.merge_check_s - since.merge_check_s,
        )


#: Process-wide tracer instance the instrumentation hooks report into.
TRACER = DispatchTracer()
