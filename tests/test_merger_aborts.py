"""Merger abort paths: an unverifiable or unhealthy fused unit must NEVER
take traffic, and its provisioned resources must be torn down."""
import threading

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FunctionSpec, FusionPolicy, OrchestratedBackend, TinyJaxBackend
from repro.core.function import FunctionInstance
from repro.core.handler import EdgeStats
from repro.core.merger import _tree_mismatch

BACKENDS = [TinyJaxBackend, OrchestratedBackend]


def deploy_pair(platform, w):
    def fn_b(ctx, params, x):
        return jnp.tanh(x @ params)

    def fn_a(ctx, params, x):
        return ctx.call("B", x @ params)

    platform.deploy(FunctionSpec("A", fn_a, w))
    platform.deploy(FunctionSpec("B", fn_b, w))


@pytest.mark.parametrize("backend_cls", BACKENDS)
def test_no_canary_abort_keeps_routing_and_detaches_unit(backend_cls):
    p = backend_cls(FusionPolicy(min_observations=1, merge_cost_s=0.0))
    try:
        deploy_pair(p, jnp.eye(8) * 0.5)
        before = {n: id(p.registry.resolve(n)) for n in ("A", "B")}
        # a hot edge exists but NO canary traffic was ever captured
        p.handler.edges[("A", "B")] = EdgeStats(sync_count=5, total_wait_s=1.0)
        p.merger.submit("A", "B")
        events = p.merger.merge_log
        assert events and not events[-1].healthy
        assert events[-1].reason == "no canary traffic captured"
        assert events[-1].checked_members == ()
        assert {n: id(p.registry.resolve(n)) for n in ("A", "B")} == before
        if backend_cls is OrchestratedBackend:
            # the never-promoted unit's pod must be gone
            live_members = {tuple(sorted(w.instance.members)) for w in p._workers.values()}
            assert ("A", "B") not in live_members
    finally:
        p.shutdown()


@pytest.mark.parametrize("backend_cls", BACKENDS)
def test_health_check_failure_never_swaps_routing(backend_cls):
    """Bad callee output in the merged unit -> abort; originals keep serving
    correct results."""
    p = backend_cls(FusionPolicy(min_observations=1, merge_cost_s=0.0, enabled=False))
    try:
        w = jnp.eye(8) * 0.5
        deploy_pair(p, w)
        x = jnp.ones((2, 8))
        ref = np.asarray(p.invoke("A", x))  # records canaries for A and B
        before = {n: id(p.registry.resolve(n)) for n in ("A", "B")}

        # Corrupt the callee's SPEC: the merged unit is built from specs, so
        # its inlined B produces garbage while the live instances are intact.
        good = p._specs["B"]
        p._specs["B"] = FunctionSpec("B", lambda ctx, params, xx: jnp.tanh(xx @ params) + 100.0, good.params)
        p.policy.enabled = True
        p.handler.edges[("A", "B")] = EdgeStats(sync_count=5, total_wait_s=1.0)
        p.merger.submit("A", "B")

        events = p.merger.merge_log
        assert events and not events[-1].healthy
        assert events[-1].reason == "health check failed"
        assert events[-1].detail.startswith("A: output float32[2, 8]: max |d| 100.0")
        assert events[-1].checked_members  # it DID replay canaries before aborting
        assert {n: id(p.registry.resolve(n)) for n in ("A", "B")} == before
        n_events = len(events)
        np.testing.assert_allclose(np.asarray(p.invoke("A", x)), ref, rtol=1e-6)
        # the failed edge is quarantined: fresh traffic re-observing the hot
        # edge must NOT re-trigger the doomed merge (control-plane spin)
        assert len(p.merger.merge_log) == n_events
        if backend_cls is OrchestratedBackend:
            live_members = {tuple(sorted(w.instance.members)) for w in p._workers.values()}
            assert ("A", "B") not in live_members
    finally:
        p.shutdown()


_LOGITS = np.array([[4.0, -0.01, 0.5]], np.float32)


@pytest.mark.parametrize("got,needle", [
    ({"logits": _LOGITS, "len": np.int32(3)}, None),
    ({"logits": _LOGITS + np.float32(0.01), "len": np.int32(3)}, None),
    # 0.05 off a logit near zero is rounding on a leaf of scale 4: accepted,
    # though an element-wise bound (0.01 + 0.02 * 0.01) would refuse it
    ({"logits": _LOGITS + np.float32(0.05), "len": np.int32(3)}, None),
    # bf16 leaves are floating too (numpy files bfloat16 under kind "V"):
    # compared within tolerance, not bit for bit
    ({"logits": jnp.asarray(_LOGITS, jnp.bfloat16), "len": np.int32(3)}, None),
    ({"logits": jnp.asarray(_LOGITS + np.float32(0.05), jnp.bfloat16), "len": np.int32(3)}, None),
    ({"logits": jnp.asarray(_LOGITS + np.float32(1.0), jnp.bfloat16), "len": np.int32(3)},
     "['logits'] float32[1, 3]: max |d| 1.0 > 0.09"),
    ({"logits": _LOGITS + np.float32(1.0), "len": np.int32(3)},
     "['logits'] float32[1, 3]: max |d| 1.0"),
    # a NaN is never within tolerance
    ({"logits": _LOGITS * np.float32([[1.0, np.nan, 1.0]]), "len": np.int32(3)},
     "['logits'] float32[1, 3]: max |d| nan"),
    ({"logits": _LOGITS, "len": np.int32(4)}, "['len']: int32 values differ"),
    ({"logits": _LOGITS[:, :2], "len": np.int32(3)}, "['logits']: shape (1, 2) vs (1, 3)"),
    ({"logits": _LOGITS}, "1 leaves vs 2"),
])
def test_tree_mismatch_names_the_leaf_and_the_gap(got, needle):
    ref = {"logits": _LOGITS, "len": np.int32(3)}
    bad = _tree_mismatch(got, ref, rtol=2e-2, atol=1e-2)
    if needle is None:
        assert bad is None
    else:
        assert bad is not None and bad.startswith(needle), bad


def test_failed_group_not_rebuilt_via_other_edges():
    """After a group fails its health check, OTHER edges resolving to the
    same member set must not pay the doomed build again."""
    p = TinyJaxBackend(FusionPolicy(min_observations=1, merge_cost_s=0.0, enabled=False))
    try:
        w = jnp.eye(8) * 0.5
        p.deploy(FunctionSpec("A", lambda ctx, params, x: ctx.call("B", x @ params), w))
        p.deploy(FunctionSpec("B", lambda ctx, params, x: ctx.call("C", x @ params), w))
        p.deploy(FunctionSpec("C", lambda ctx, params, x: jnp.tanh(x @ params), w))
        p.invoke("A", jnp.ones((2, 8)))  # canaries for A, B and C

        good = p._specs["C"]
        p._specs["C"] = FunctionSpec("C", lambda ctx, params, x: jnp.tanh(x @ params) + 100.0, good.params)
        p.policy.enabled = True
        p.policy.commit("A", "B")  # as if an earlier A+B merge was healthy
        p.handler.edges[("B", "C")] = EdgeStats(sync_count=5, total_wait_s=1.0)
        p.merger.submit("B", "C")  # builds {A,B,C}, health check fails
        assert len(p.merger.merge_log) == 1 and not p.merger.merge_log[0].healthy
        assert set(p.merger.merge_log[0].members) == {"A", "B", "C"}

        p.handler.edges[("A", "C")] = EdgeStats(sync_count=5, total_wait_s=1.0)
        p.merger.submit("A", "C")  # same doomed group via a different edge
        assert len(p.merger.merge_log) == 1, "doomed group must not be rebuilt"
    finally:
        p.shutdown()


def test_detach_instance_stops_never_promoted_worker():
    p = OrchestratedBackend(FusionPolicy(enabled=False))
    try:
        p.deploy(FunctionSpec("B", lambda ctx, params, x: x + 1, None))
        spec = p.spec_of("B")
        candidate = FunctionInstance({"B": spec}, p)
        p.attach_instance(candidate)
        worker = p._workers[candidate.instance_id]
        assert worker.thread.is_alive()

        p.detach_instance(candidate)
        worker.thread.join(timeout=10)
        assert not worker.thread.is_alive(), "detached pod's request loop must exit"
        assert candidate.instance_id not in p._workers
        # routing never pointed at the candidate; B still serves
        assert int(p.invoke("B", jnp.int32(1))) == 2
    finally:
        p.shutdown()


def test_detach_is_noop_for_unknown_instance():
    p = OrchestratedBackend(FusionPolicy(enabled=False))
    try:
        p.deploy(FunctionSpec("B", lambda ctx, params, x: x, None))
        ghost = FunctionInstance({"B": p.spec_of("B")}, p)  # never attached
        p.detach_instance(ghost)  # must not raise or disturb live workers
        assert int(p.invoke("B", jnp.int32(7))) == 7
    finally:
        p.shutdown()
