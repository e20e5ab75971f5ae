"""Warm provisioning: executable-index reuse, scale-to-zero park/resurrect,
and bit-exactness of restored instances on every serving path.

"Bit-exact" here always means: the restored instance runs the SAME XLA
executable (an executable-index hit, counted by ``provision_profile``) on
digest-verified restored params — so outputs are ``np.array_equal``, not
merely allclose. Fused vs UNFUSED programs are different XLA graphs and are
deliberately never compared bit-for-bit.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.dispatch import TRACER
from repro.configs import get_arch, reduced_config
from repro.core import FunctionSpec, FusionPolicy, TinyJaxBackend
from repro.launch.compile_cache import (
    EXECUTABLE_INDEX,
    ExecutableIndex,
    environment_key,
    members_digest,
    spec_digest,
)
from repro.models.model import build_model
from repro.serving.engine import ServingEngine


def _leaf(ctx, params, x):
    return jnp.tanh(x @ params["w"])


def _chain_head(ctx, params, x):
    return ctx.call("L", jnp.tanh(x @ params["w"]))


def _weights(seed, n=32):
    rs = np.random.RandomState(seed)
    return {"w": jnp.asarray(rs.randn(n, n).astype(np.float32) * 0.1)}


@pytest.fixture(autouse=True)
def _fresh_index():
    EXECUTABLE_INDEX.clear()
    yield
    EXECUTABLE_INDEX.clear()


# ------------------------------------------------------------ digest + index


def test_spec_digest_stable_and_distinguishes_params_shape():
    spec = FunctionSpec("f", _leaf, _weights(0))
    assert spec_digest(spec) == spec_digest(spec)  # memoized, deterministic
    # params are call arguments, not digest inputs: same fn = same digest
    assert spec_digest(spec) == spec_digest(FunctionSpec("f", _leaf, _weights(1)))
    assert spec_digest(spec) != spec_digest(FunctionSpec("g", _leaf, _weights(0)))


def test_spec_digest_sees_closure_values():
    """Two stages built from ONE factory share code objects and differ only
    in their closure cells — the exact aliasing a closure-blind digest would
    collide on (and then serve stage 1's executable for stage 0)."""

    def make_stage(scale):
        def fn(ctx, params, x):
            return x * scale

        return fn

    s0 = FunctionSpec("s", make_stage(2.0), {})
    s1 = FunctionSpec("s", make_stage(3.0), {})
    assert spec_digest(s0) != spec_digest(s1)


def test_members_digest_order_independent():
    a = FunctionSpec("a", _leaf, _weights(0))
    b = FunctionSpec("b", _leaf, _weights(1))
    assert members_digest({"a": a, "b": b}) == members_digest({"b": b, "a": a})


def test_environment_key_covers_dispatch_mode():
    assert len(environment_key()) == 4
    assert environment_key() == environment_key()


@pytest.fixture
def _restore_cache_config():
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield
    from jax.experimental.compilation_cache import compilation_cache

    for k, v in saved.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch, _restore_cache_config):
    """Unset JAX_COMPILATION_CACHE_DIR: the cache goes to the checkout's own
    ``.jax_compile_cache`` — the same path on every call, so a later process
    finds what an earlier one wrote."""
    import pathlib

    from repro.launch import compile_cache as cc

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = pathlib.Path(__file__).resolve().parents[1]
    assert cc.cache_dir() == str(checkout / ".jax_compile_cache") == cc.cache_dir()
    assert cc.enable_persistent_cache() == cc.DEFAULT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == cc.DEFAULT_CACHE_DIR
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path, _restore_cache_config):
    """A set JAX_COMPILATION_CACHE_DIR wins, and the code sets no other
    directory (JAX reads the variable itself)."""
    from repro.launch import compile_cache as cc

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    before = jax.config.jax_compilation_cache_dir
    assert cc.cache_dir() == str(tmp_path / "cc")
    assert cc.enable_persistent_cache() == str(tmp_path / "cc")
    assert (tmp_path / "cc").is_dir()
    assert jax.config.jax_compilation_cache_dir == before
    cc.disable_persistent_cache()
    assert not jax.config.jax_enable_compilation_cache


def test_executable_index_lru_and_counters():
    idx = ExecutableIndex(max_entries=2)
    e = dataclasses.make_dataclass("E", [("compile_s", float)])(0.5)
    idx.insert(("k1",), e)
    idx.insert(("k2",), e)
    assert idx.lookup(("k1",)) is e  # refreshes k1's recency
    idx.insert(("k3",), e)  # evicts k2, the least recently used
    assert idx.lookup(("k2",)) is None
    assert idx.lookup(("k1",)) is e
    assert idx.lookup(None) is None  # undigestable specs never hit
    s = idx.stats()
    assert s["entries"] == 2 and s["evictions"] == 1
    assert s["hits"] == 2 and s["misses"] == 1
    assert s["saved_s"] == pytest.approx(1.0)


def test_rebuilt_instance_hits_index_and_is_bit_identical():
    """The tentpole invariant: tearing a platform down and rebuilding the
    same spec reuses the compiled executable (0 recompiles) and therefore
    reproduces outputs bit-for-bit."""
    spec = FunctionSpec("f", _leaf, _weights(0))
    x = jnp.ones((4, 32), jnp.float32)

    p1 = TinyJaxBackend(FusionPolicy(enabled=False))
    try:
        p1.deploy(spec)
        r1 = np.asarray(p1.invoke("f", x))
        inst1 = p1.registry.resolve("f")
        assert inst1.provision_profile()["cache_misses"] == 1
    finally:
        p1.shutdown()

    p2 = TinyJaxBackend(FusionPolicy(enabled=False))
    try:
        p2.deploy(spec)
        base = TRACER.snapshot()
        TRACER.arm()
        try:
            r2 = np.asarray(p2.invoke("f", x))
        finally:
            TRACER.disarm()
        assert TRACER.delta(base).compiles == 0
        inst2 = p2.registry.resolve("f")
        prof = inst2.provision_profile()
        assert prof["cache_hits"] == 1 and prof["cache_misses"] == 0
        np.testing.assert_array_equal(r1, r2)
    finally:
        p2.shutdown()


def test_effectful_program_never_enters_index():
    """A program with io_callback effects closes over ITS platform — serving
    it to another platform would route async calls into a dead object. The
    index must refuse such entries."""

    def async_head(ctx, params, x):
        ctx.call_async("sink", x)
        return jnp.tanh(x @ params["w"])

    def sink(ctx, params, x):
        return x

    specs = {"hd": FunctionSpec("hd", async_head, _weights(0)),
             "sink": FunctionSpec("sink", sink, {})}
    x = jnp.ones((2, 32), jnp.float32)
    p1 = TinyJaxBackend(FusionPolicy(enabled=False))
    try:
        p1.deploy(specs["hd"])
        p1.deploy(specs["sink"])
        p1.invoke("hd", x)
    finally:
        p1.shutdown()
    # same specs on a fresh platform: if hd's effectful program had been
    # indexed, this instance would hit it — and run callbacks into p1
    p2 = TinyJaxBackend(FusionPolicy(enabled=False))
    try:
        p2.deploy(specs["hd"])
        p2.deploy(specs["sink"])
        p2.invoke("hd", x)
        prof = p2.registry.resolve("hd").provision_profile()
        assert prof["cache_hits"] == 0 and prof["cache_misses"] == 1
    finally:
        p2.shutdown()


# ---------------------------------------------------- merge/split churn


def _drive_fusion(platform, x, n=4):
    for _ in range(n):
        out = platform.invoke("H", x)
    platform.merger.wait_idle()
    return np.asarray(out)


def test_merge_split_remerge_zero_recompiles():
    """Satellite 1 + tentpole: after the first merge cycle, split and
    re-merge are both served from the executable index — churn restores,
    never rebuilds."""
    policy = FusionPolicy(min_observations=2, merge_cost_s=0.0,
                          min_group_age_s=0.0, remerge_backoff_s=0.0)
    p = TinyJaxBackend(policy)
    x = jnp.ones((4, 32), jnp.float32)
    try:
        p.deploy(FunctionSpec("H", _chain_head, _weights(0)))
        p.deploy(FunctionSpec("L", _leaf, _weights(1)))
        fused_ref = _drive_fusion(p, x)
        merges = [m for m in p.merger.merge_log if m.healthy]
        assert len(merges) == 1 and merges[0].warm is False  # cold first build

        base = TRACER.snapshot()
        TRACER.arm()
        try:
            ev = p.merger.split(frozenset({"H", "L"}), [{"H"}, {"L"}])
            assert ev is not None and ev.healthy and ev.warm
            fused_again = _drive_fusion(p, x)
        finally:
            TRACER.disarm()
        assert TRACER.delta(base).compiles == 0
        merges = [m for m in p.merger.merge_log if m.healthy]
        assert len(merges) == 2 and merges[1].warm is True
        # same executable, same params -> bit-identical fused outputs
        np.testing.assert_array_equal(fused_ref, fused_again)
        stats = p.stats()["provisioning"]
        assert stats["counts"]["merge"] == 2 and stats["counts"]["split"] == 1
        assert stats["compile_cache"]["hits"] > 0
    finally:
        p.shutdown()


# ------------------------------------------------------ park + resurrect


def test_scale_to_zero_resurrect_bit_identical_and_billed(tmp_path):
    p = TinyJaxBackend(FusionPolicy(enabled=False), snapshot_dir=str(tmp_path))
    x = jnp.ones((4, 32), jnp.float32)
    try:
        p.deploy(FunctionSpec("f", _leaf, _weights(0)))
        ref = np.asarray(p.invoke("f", x))
        parked = p.scale_to_zero("f")
        assert parked == ("f",)
        assert p.provisioning_stats()["parked"] == ["f"]
        assert p.registry.get("f") is None  # route is gone, RAM released
        assert p.snapshots.stats()["puts"] == 1

        base = TRACER.snapshot()
        TRACER.arm()
        try:
            got = np.asarray(p.invoke("f", x))
        finally:
            TRACER.disarm()
        assert TRACER.delta(base).compiles == 0
        np.testing.assert_array_equal(ref, got)
        assert p.provisioning_stats()["parked"] == []

        prov = p.meter.summary()["provisioning"]
        # resurrect time is billed; the parked idle time is not a record at all
        assert prov["billed_s"] > 0.0
        kinds = [r.kind for r in p.meter.provisioning]
        assert kinds.count("park") == 1 and kinds.count("resurrect") == 1
        billed = {r.kind: r.billed for r in p.meter.provisioning}
        assert billed["resurrect"] is True and billed["park"] is False
        rez = [r for r in p.meter.provisioning if r.kind == "resurrect"][0]
        assert rez.warm is True  # executable came from the index
    finally:
        p.shutdown()


def test_invocation_billing_unchanged_by_provisioning(tmp_path):
    """Provisioning is a separate line item: total_gb_s must cover exactly
    the invocation records, with or without parks in the session."""
    p = TinyJaxBackend(FusionPolicy(enabled=False), snapshot_dir=str(tmp_path))
    x = jnp.ones((4, 32), jnp.float32)
    try:
        p.deploy(FunctionSpec("f", _leaf, _weights(0)))
        p.invoke("f", x)
        p.scale_to_zero("f")
        p.invoke("f", x)
        s = p.meter.summary()
        with p.meter._lock:
            invocation_total = sum(r.gb_seconds for r in p.meter.records)
        assert s["total_gb_s"] == pytest.approx(invocation_total)
    finally:
        p.shutdown()


def test_resurrect_of_fused_group_re_fuses_bit_identical(tmp_path):
    """Round trip: merge -> park the fused unit -> resurrect -> re-merge.
    The re-fused unit must reuse the first fused executable (index hit) and
    reproduce fused outputs bit-for-bit."""
    policy = FusionPolicy(min_observations=2, merge_cost_s=0.0,
                          min_group_age_s=0.0, remerge_backoff_s=0.0)
    p = TinyJaxBackend(policy, snapshot_dir=str(tmp_path))
    x = jnp.ones((4, 32), jnp.float32)
    try:
        p.deploy(FunctionSpec("H", _chain_head, _weights(0)))
        p.deploy(FunctionSpec("L", _leaf, _weights(1)))
        fused_ref = _drive_fusion(p, x)
        assert any(m.healthy for m in p.merger.merge_log)

        parked = p.scale_to_zero("H")  # parks the whole fused {H, L} unit
        assert set(parked) == {"H", "L"}
        assert set(p.provisioning_stats()["parked"]) == {"H", "L"}

        fused_again = _drive_fusion(p, x)  # resurrect singletons, re-fuse
        merges = [m for m in p.merger.merge_log if m.healthy]
        assert len(merges) == 2 and merges[1].warm is True
        np.testing.assert_array_equal(fused_ref, fused_again)
        counts = p.provisioning_stats()["counts"]
        assert counts["park"] == 1 and counts["resurrect"] >= 1
    finally:
        p.shutdown()


def test_idle_park_tick_parks_and_invoke_resurrects(tmp_path):
    """Scale-to-zero end to end on the reconciler path: an idle function is
    parked by the tick hook, and the next invoke transparently resurrects."""
    from repro.scheduler.clock import VirtualClock

    clock = VirtualClock()
    p = TinyJaxBackend(FusionPolicy(enabled=False), snapshot_dir=str(tmp_path),
                       idle_park_s=5.0, clock=clock)
    x = jnp.ones((4, 32), jnp.float32)
    try:
        p.deploy(FunctionSpec("f", _leaf, _weights(0)))
        ref = np.asarray(p.invoke("f", x))
        clock.advance(10.0)
        p._idle_park_tick()
        assert p.provisioning_stats()["parked"] == ["f"]
        got = np.asarray(p.invoke("f", x))
        np.testing.assert_array_equal(ref, got)
        assert p.provisioning_stats()["parked"] == []
    finally:
        p.shutdown()


# -------------------------------------------------- serving-path bit-exact


def _engine(tmp_path, *, kv_pages=0, fused=False):
    cfg = reduced_config(get_arch("llama3.2-1b"))
    model = build_model(cfg)
    platform = TinyJaxBackend(
        FusionPolicy(min_observations=2, merge_cost_s=0.0, enabled=fused),
        snapshot_dir=str(tmp_path),
    )
    engine = ServingEngine(model, platform, max_len=48,
                           kv_pages=kv_pages, kv_page_size=16)
    return engine, platform


def test_dense_and_paged_chains_resurrect_bit_identical(tmp_path):
    """One engine (with a KV arena), two serving paths: plain dense decode
    and paged decode must BOTH reproduce outputs bit-for-bit after a full
    park -> resurrect cycle."""
    engine, platform = _engine(tmp_path, kv_pages=32)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 8), 0,
                                engine.cfg.vocab_size, jnp.int32)
    try:
        ref, _ = engine.generate({"tokens": tokens}, steps=6)
        parked = engine.scale_to_zero()
        assert set(parked) == set(engine.chain_names())
        got, _ = engine.generate({"tokens": tokens}, steps=6)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

        ref_p, _ = engine.generate_paged({"tokens": tokens[:1]}, steps=6)
        assert engine.scale_to_zero()
        got_p, _ = engine.generate_paged({"tokens": tokens[:1]}, steps=6)
        np.testing.assert_array_equal(np.asarray(ref_p), np.asarray(got_p))
    finally:
        platform.shutdown()


def test_fused_chain_resurrects_bit_identical(tmp_path):
    engine, platform = _engine(tmp_path, fused=True)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                engine.cfg.vocab_size, jnp.int32)
    try:
        ref, _ = engine.generate({"tokens": tokens}, steps=8)
        platform.merger.wait_idle()
        assert any(m.healthy for m in platform.merger.merge_log)
        # a second pass on the settled (fused) chain is the reference
        ref, _ = engine.generate({"tokens": tokens}, steps=8)
        parked = engine.scale_to_zero()
        assert parked
        # resurrect + let the chain re-fuse, then compare the settled outputs
        engine.generate({"tokens": tokens}, steps=8)
        platform.merger.wait_idle()
        got, _ = engine.generate({"tokens": tokens}, steps=8)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))
    finally:
        platform.shutdown()


