"""Serving engine on the Provuse platform: chain correctness under fusion."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch, reduced_config
from repro.core import FusionPolicy, TinyJaxBackend
from repro.models.model import build_model
from repro.models.params import init_params
from repro.serving.engine import ServingEngine


def direct_generate(model, params, tokens, steps, max_len):
    """Reference: generate WITHOUT the platform (plain model calls)."""
    from repro.configs.base import ShapeConfig

    logits, cache = jax.jit(model.prefill_fn)(params, {"tokens": tokens})
    t = tokens.shape[1]
    # pad cache seq dim to max_len
    def grow(x):
        if x.ndim >= 3 and x.shape[-3] == t:
            pad = [(0, 0)] * x.ndim
            pad[-3] = (0, max_len - t)
            return jnp.pad(x, pad)
        return x

    cache = jax.tree.map(grow, cache)
    cur = jnp.full((tokens.shape[0],), t, jnp.int32)
    out = [jnp.argmax(logits, -1)[:, None].astype(jnp.int32)]
    dec = jax.jit(model.decode_fn)
    for _ in range(steps - 1):
        logits, cache = dec(params, {"tokens": out[-1], "cur_len": cur}, cache)
        cur = cur + 1
        out.append(jnp.argmax(logits, -1)[:, None].astype(jnp.int32))
    return jnp.concatenate(out, axis=1)


def deployed_params(engine):
    """The model's full tree, rebuilt from the only copy there is: the
    weights the deployed stages hold."""
    spec = engine.platform.spec_of
    head = spec(engine.chain_names()[-1]).params
    groups = [spec(n).params for n in engine.group_names]
    return {"embed": head["embed"], "ln_f": head["ln_f"],
            "blocks": jax.tree.map(lambda *xs: jnp.concatenate(xs), *groups)}


def test_chain_generation_matches_direct_model():
    cfg = reduced_config(get_arch("llama3.2-1b"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0))
    try:
        engine = ServingEngine(model, platform, max_len=48)
        params = deployed_params(engine)
        tokens = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0, cfg.vocab_size, jnp.int32)
        got, _ = engine.generate({"tokens": tokens}, steps=10)
        expect = direct_generate(model, params, tokens, 10, 48)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(expect))
        # fusion actually happened during generation
        assert any(m.healthy for m in platform.merger.merge_log)
    finally:
        platform.shutdown()


def test_chain_fuses_to_single_instance_and_latency_drops():
    cfg = reduced_config(get_arch("llama3.2-1b"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0))
    try:
        engine = ServingEngine(model, platform, max_len=48)
        tokens = jnp.ones((1, 8), jnp.int32)
        _, lat = engine.generate({"tokens": tokens}, steps=16)
        live = platform.registry.live_instances()
        assert len(live) == 1, f"chain should fully fuse, got {live}"
        assert np.median(lat[-3:]) < np.median(lat[:3])
    finally:
        platform.shutdown()


def test_paged_generate_bit_identical():
    """generate() outputs are bit-identical pre/post KV paging: the paged
    decode gathers pages to the same width the dense cache has, and masked
    positions contribute exact zeros — same program, same values."""
    cfg = reduced_config(get_arch("llama3.2-1b"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0))
    try:
        engine = ServingEngine(model, platform, max_len=48, kv_pages=32, kv_page_size=16)
        tokens = jax.random.randint(jax.random.PRNGKey(5), (2, 11), 0, cfg.vocab_size, jnp.int32)
        dense, _ = engine.generate({"tokens": tokens}, steps=12)
        paged, _ = engine.generate_paged({"tokens": tokens}, steps=12)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged))
        # decode crossed a page boundary (11 + 11 tokens > page 16)
        assert engine.arena.used_pages() == 0  # pages freed on exit
        engine.arena.check_consistency()
        # a second paged run after the arena was recycled still matches
        paged2, _ = engine.generate_paged({"tokens": tokens}, steps=12)
        np.testing.assert_array_equal(np.asarray(dense), np.asarray(paged2))
    finally:
        platform.shutdown()


def test_paging_unsupported_for_ssm():
    cfg = reduced_config(get_arch("mamba2-370m"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(enabled=False))
    try:
        engine = ServingEngine(model, platform, max_len=32)
        assert not engine.paging_supported
        with pytest.raises(ValueError):
            engine.enable_paging(8)
    finally:
        platform.shutdown()


def test_encdec_two_function_app():
    cfg = reduced_config(get_arch("seamless-m4t-medium"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(min_observations=1, merge_cost_s=0.0))
    try:
        engine = ServingEngine(model, platform, max_len=32)
        inputs = {
            "src_embeds": (jax.random.normal(jax.random.PRNGKey(0), (2, 8, cfg.d_model)) * 0.02).astype(jnp.bfloat16),
            "tokens": jnp.zeros((2, 1), jnp.int32),
        }
        toks, _ = engine.generate(inputs, steps=6)
        assert toks.shape == (2, 6)
        assert jnp.all((toks >= 0) & (toks < cfg.vocab_size))
        merged = [m for m in platform.merger.merge_log if m.healthy]
        assert merged and len(merged[0].members) == 2  # encoder + decoder fused
    finally:
        platform.shutdown()


def test_hybrid_monolithic_chain():
    cfg = reduced_config(get_arch("zamba2-7b"))
    model = build_model(cfg)
    platform = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0))
    try:
        engine = ServingEngine(model, platform, max_len=32)
        tokens = jnp.ones((1, 8), jnp.int32)
        toks, _ = engine.generate({"tokens": tokens}, steps=5)
        assert toks.shape == (1, 5)
    finally:
        platform.shutdown()


@pytest.mark.parametrize("step", ["prefill_chunk", "decode"])
def test_moe_stage_computes_the_same_bits_glued_or_fused(step):
    """The merge health check compares a stage run as interpreter glue with
    the same stage inside a fused unit. For a MoE stage (Mellum2's pattern:
    windowed and YaRN layers, dropless experts) on a prompt past its window,
    both give the same arena and expert row counts bit for bit, and the same
    logits: the routing does not move with the compile context."""
    from repro.core.function import FunctionInstance

    cfg = reduced_config(get_arch("mellum2-12b-a2.5b"))
    platform = TinyJaxBackend()
    platform.merger.submit = lambda *a, **k: None  # keep every stage glued
    try:
        eng = ServingEngine(build_model(cfg), platform, max_len=128, seed=5,
                            kv_pages=24, kv_page_size=8)
        prompt = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 40)).astype(np.int32)
        job = eng.begin_prefill_paged("a", {"tokens": prompt})
        while eng.prefill_chunk_paged(job, 32) is None:
            pass
        if step == "decode":  # slot 1 live, slots 0, 2, 3 empty
            eng.arena.extend("a", 41)
            table = np.zeros((4, eng.block_width), np.int32)
            table[1] = eng.arena.block_row("a", eng.block_width)
            tokens = np.zeros((4, 1), np.int32)
            tokens[1, 0] = 7
            eng.paged_decode_step(jnp.asarray(tokens), np.asarray([0, 40, 0, 0], np.int32), table)
        g1, head = f"{cfg.name}/g1", f"{cfg.name}/head"
        canary = platform.handler.canary(g1)
        glued = platform._invoke_with_retry(g1, canary)
        fused = FunctionInstance({n: platform.spec_of(n) for n in (g1, head)}, platform)
        assert fused.get_compiled(g1, canary) is not None  # one compiled program
        got = fused.execute(g1, canary)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(glued)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        platform.shutdown()
