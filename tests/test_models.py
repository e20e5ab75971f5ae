"""Per-arch smoke tests (reduced configs, one fwd/train step on CPU) +
model-level correctness properties."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, reduced_config
from repro.configs.base import ShapeConfig
from repro.models.model import build_model
from repro.models.params import init_params

TRAIN = ShapeConfig("smoke_train", 32, 2, "train")
PREFILL = ShapeConfig("smoke_prefill", 32, 2, "prefill")
DECODE = ShapeConfig("smoke_decode", 32, 2, "decode")

# Archs whose reduced config still takes minutes of XLA compile on CPU; their
# smoke cells run via `-m slow` (the hybrid family keeps tier-1 coverage
# through tests/test_serving.py::test_hybrid_monolithic_chain).
SLOW_COMPILE_ARCHS = {"zamba2-7b"}


def arch_params(archs):
    return [
        pytest.param(a, marks=pytest.mark.slow) if a in SLOW_COMPILE_ARCHS else a
        for a in archs
    ]


@pytest.mark.parametrize("arch", arch_params(sorted(ARCHS)))
def test_arch_smoke_train_step(arch):
    """REDUCED config of the same family: one loss+grad step, shapes + no NaNs."""
    cfg = reduced_config(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = model.make_inputs(TRAIN, jax.random.PRNGKey(1))
    (loss, metrics), grads = jax.jit(jax.value_and_grad(model.loss_fn, has_aux=True))(params, batch)
    assert jnp.isfinite(loss), f"{arch}: loss not finite"
    assert all(jnp.isfinite(g).all() for g in jax.tree.leaves(grads)), f"{arch}: NaN grads"
    assert float(metrics["loss"]) == pytest.approx(float(loss))


@pytest.mark.parametrize("arch", arch_params(sorted(ARCHS)))
def test_arch_smoke_serve_paths(arch):
    cfg = reduced_config(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    logits, cache = jax.jit(model.prefill_fn)(params, model.make_inputs(PREFILL, jax.random.PRNGKey(1)))
    assert logits.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits).all(), f"{arch}: prefill NaNs"
    dec_in = model.make_inputs(DECODE, jax.random.PRNGKey(2))
    dec_cache = init_params(model.cache_defs(DECODE), jax.random.PRNGKey(3))
    logits2, new_cache = jax.jit(model.decode_fn)(params, dec_in, dec_cache)
    assert logits2.shape == (2, cfg.vocab_size)
    assert jnp.isfinite(logits2).all(), f"{arch}: decode NaNs"
    assert jax.tree_util.tree_structure(new_cache) == jax.tree_util.tree_structure(dec_cache)


@pytest.mark.parametrize("arch", arch_params(["llama3.2-1b", "stablelm-1.6b", "mamba2-370m", "zamba2-7b"]))
def test_prefill_then_decode_matches_full_forward(arch):
    """Serving-path correctness: prefill a prompt, decode the next token —
    logits must match a prefill over the extended prompt (same cache math)."""
    cfg = reduced_config(get_arch(arch))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    t = 16
    tokens = jax.random.randint(jax.random.PRNGKey(5), (2, t + 1), 0, cfg.vocab_size, jnp.int32)

    logits_a, cache = jax.jit(model.prefill_fn)(params, {"tokens": tokens[:, :t]})
    # grow attention caches by one slot so decode can write at position t
    def grow(x):
        if x.ndim >= 3 and x.shape[-3] == t:  # (.., B, S, KV, hd) seq dim
            pad = [(0, 0)] * x.ndim
            pad[-3] = (0, 1)
            return jnp.pad(x, pad)
        return x
    cache = jax.tree.map(grow, cache)
    batch = {"tokens": tokens[:, t:], "cur_len": jnp.full((2,), t, jnp.int32)}
    logits_b, _ = jax.jit(model.decode_fn)(params, batch, cache)

    logits_full, _ = jax.jit(model.prefill_fn)(params, {"tokens": tokens})
    # SSM-family decode uses the recurrent form vs the chunked dual form in
    # prefill: mathematically identical, but bf16 rounding reorders through
    # exp() decay products -> wider tolerance than for attention archs.
    loose = cfg.family in ("ssm", "hybrid")
    np.testing.assert_allclose(
        np.asarray(logits_b, np.float32),
        np.asarray(logits_full, np.float32),
        rtol=0.2 if loose else 3e-2,
        atol=0.5 if loose else 3e-2,
    )


def test_causality_future_tokens_do_not_change_past():
    cfg = reduced_config(get_arch("llama3.2-1b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    t = 16
    tok1 = jax.random.randint(jax.random.PRNGKey(1), (1, t), 0, cfg.vocab_size, jnp.int32)
    tok2 = tok1.at[:, -1].set((tok1[:, -1] + 1) % cfg.vocab_size)
    # last-token logits after t-1 tokens must be identical
    l1, _ = jax.jit(model.prefill_fn)(params, {"tokens": tok1[:, : t - 1]})
    l2, _ = jax.jit(model.prefill_fn)(params, {"tokens": tok2[:, : t - 1]})
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))


def test_moe_is_dropless_with_ample_capacity():
    """With capacity_factor >> 1, MoE output == explicit per-token loop."""
    import dataclasses

    from repro.models import moe as moe_mod

    cfg = dataclasses.replace(
        reduced_config(get_arch("qwen3-moe-30b-a3b")), capacity_factor=8.0
    )
    defs = moe_mod.moe_defs(cfg)
    params = init_params(defs, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model), jnp.float32).astype(jnp.bfloat16)
    y, metrics = moe_mod.apply_moe(params, x, cfg)
    assert float(metrics["moe_dropped"]) == 0.0

    # explicit reference: per-token top-k expert mix
    xf = x.reshape(-1, cfg.d_model)
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, -1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = w / w.sum(-1, keepdims=True)
    outs = []
    for i in range(xf.shape[0]):
        acc = 0
        for j in range(cfg.num_experts_per_tok):
            eidx = int(idx[i, j])
            gate = jax.nn.silu((xf[i] @ params["wi_gate"][eidx]).astype(jnp.float32))
            up = (xf[i] @ params["wi_up"][eidx]).astype(jnp.float32)
            acc = acc + float(w[i, j]) * ((gate * up).astype(jnp.bfloat16) @ params["wo"][eidx]).astype(jnp.float32)
        outs.append(acc)
    expect = jnp.stack(outs).reshape(2, 8, cfg.d_model)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), np.asarray(expect, np.float32), rtol=5e-2, atol=5e-2
    )


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_moe_dropless_keeps_every_route_under_skew_and_skips_empty_rows(monkeypatch, path):
    """Every token routes to the same two experts (a capacity layer would
    drop most of them), and two batch slots are empty: each valid row
    equals the explicit top-k mix, an empty row gets nothing, and the row
    counts hold exactly the valid rows' routes."""
    from repro.kernels import ops
    from repro.models import moe as moe_mod

    monkeypatch.setattr(ops, "_mode", lambda: path)
    cfg = reduced_config(get_arch("mellum2-12b-a2.5b"))
    params = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
    # rows near the all-ones direction, which experts 2 and 5 favour
    skew = jnp.zeros((cfg.num_experts,)).at[jnp.array([2, 5])].set(jnp.array([0.5, 0.4]))
    params["router"] = params["router"] * 0.1 + skew
    b, t = 4, 8
    x = (1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(1), (b, t, cfg.d_model))).astype(jnp.bfloat16)
    valid = jnp.ones((b, t), bool).at[jnp.array([1, 3])].set(False)
    y, metrics = moe_mod.apply_moe(params, x, cfg, valid=valid)

    rows = np.asarray(metrics["expert_rows"])
    assert rows.sum() == 2 * cfg.num_experts_per_tok * t
    assert rows[2] == rows[5] == 2 * t  # every valid token, none dropped
    np.testing.assert_array_equal(np.asarray(y)[1], 0.0)
    np.testing.assert_array_equal(np.asarray(y)[3], 0.0)
    xf = x.reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(xf.astype(jnp.float32) @ params["router"], -1)
    w, idx = jax.lax.top_k(probs, cfg.num_experts_per_tok)
    w = w / w.sum(-1, keepdims=True)
    for i in np.flatnonzero(np.asarray(valid).reshape(-1)):
        acc = 0
        for j in range(cfg.num_experts_per_tok):
            e = int(idx[i, j])
            gate = jax.nn.silu((xf[i] @ params["wi_gate"][e]).astype(jnp.float32))
            up = (xf[i] @ params["wi_up"][e]).astype(jnp.float32)
            acc = acc + float(w[i, j]) * ((gate * up).astype(jnp.bfloat16) @ params["wo"][e]).astype(jnp.float32)
        np.testing.assert_allclose(np.asarray(y.reshape(-1, cfg.d_model)[i], np.float32),
                                   np.asarray(acc), rtol=5e-2, atol=5e-2)


def test_moe_route_picks_on_the_logits_with_the_renormalised_gates():
    """The experts are the top k of the router logits and the gates the
    softmax of those k: the published rule (top k of the softmax,
    renormalised), taken on the matmul's output so that a near tie does
    not hang on how a program fuses the softmax."""
    from repro.models import moe as moe_mod

    cfg = reduced_config(get_arch("mellum2-12b-a2.5b"))
    params = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model), jnp.float32)
    probs, w, idx = moe_mod._route(params, x, cfg.num_experts_per_tok)
    ref_p = jax.nn.softmax(x @ params["router"], -1)
    ref_w, ref_idx = jax.lax.top_k(ref_p, cfg.num_experts_per_tok)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ref_idx))
    np.testing.assert_allclose(np.asarray(w), np.asarray(ref_w / ref_w.sum(-1, keepdims=True)),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(probs), np.asarray(ref_p), rtol=1e-5, atol=1e-7)


def test_moe_router_weights_normalized():
    cfg = reduced_config(get_arch("phi3.5-moe-42b-a6.6b"))
    from repro.models import moe as moe_mod

    params = init_params(moe_mod.moe_defs(cfg), jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 16, cfg.d_model)).astype(jnp.bfloat16)
    y, metrics = moe_mod.apply_moe(params, x, cfg)
    assert y.shape == x.shape
    assert float(metrics["moe_aux"]) > 0.0  # aux loss is live


@pytest.mark.parametrize("path", ["ref", "interpret"])
def test_moe_paged_decode_reads_the_stacked_experts_in_place(monkeypatch, path):
    """The paged decode scan keeps a MoE stack's expert matrices whole and
    reads each layer's in place: the same logits, arena and expert row
    counts, bit for bit, as a per-layer loop that slices every layer's
    weights out of the stack, both compiled as the platform compiles a
    stage. Two slots are live (one past its window), two are empty."""
    import functools

    from repro.core.function import COMPILER_OPTIONS
    from repro.kernels import ops
    from repro.models import transformer as tfm
    from repro.models.layers import apply_norm, unembed

    monkeypatch.setattr(ops, "_mode", lambda: path)
    cfg = reduced_config(get_arch("mellum2-12b-a2.5b"))
    n, pages, page = cfg.num_layers, 24, 8
    model = build_model(cfg)
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    params = init_params(tfm.stack_block_defs(cfg, "moe", n), ks[0])
    head = init_params({k: model.param_defs[k] for k in ("embed", "ln_f")}, ks[1])
    shape = (n, pages, cfg.num_kv_heads, page, cfg.d_head)
    arena = {kv: jax.random.normal(k, shape, jnp.float32).astype(jnp.bfloat16)
             for kv, k in zip("kv", ks[2:4])}
    table = np.zeros((4, 6), np.int32)
    table[0] = [1, 2, 3, 4, 5, 6]  # 37 tokens, past the 16-token window
    table[2, :2] = [7, 8]
    table = jnp.asarray(table)
    cur_len = jnp.array([37, 0, 10, 0], jnp.int32)
    x = jax.random.normal(ks[4], (4, 1, cfg.d_model), jnp.float32).astype(jnp.bfloat16)

    def logits(h):
        return unembed(head["embed"], apply_norm(head["ln_f"], h, cfg))[:, 0]

    stage = functools.partial(jax.jit, compiler_options=COMPILER_OPTIONS)

    @stage
    def in_place(params, x, arena):
        h, arena, metrics = tfm.apply_stack_decode_paged(params, x, arena, table, cfg, "moe",
                                                         None, cur_len)
        return logits(h), arena, metrics[tfm.EXPERT_ROWS]

    @stage
    def per_layer(params, x, arena):
        ks_, vs_, rows = [], [], []
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], params)
            x, k, v, m = tfm.apply_block_decode_paged(lp, x, arena["k"][i], arena["v"][i], table,
                                                      cfg, "moe", None, cur_len, True, i)
            ks_.append(k)
            vs_.append(v)
            rows.append(m[tfm.EXPERT_ROWS])
        return logits(x), {"k": jnp.stack(ks_), "v": jnp.stack(vs_)}, jnp.stack(rows)

    got, want = in_place(params, x, arena), per_layer(params, x, arena)
    assert int(want[2].sum()) == 2 * cfg.num_experts_per_tok * n  # two live slots route
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
