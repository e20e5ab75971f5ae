"""Pre-LN transformer blocks and scanned stacks.

Layers are stacked on a leading 'layers' axis and applied with
``jax.lax.scan`` so the HLO is O(1) in depth (critical: the dry-run compiles
88-layer/34B programs on a CPU host). ``jax.checkpoint`` wraps the block body
when ``cfg.remat`` — activation memory is one residual stream per layer
boundary, everything else recomputed in backward.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_mlp, apply_norm, mlp_defs, norm_defs
from repro.models.params import ParamDef, stack_defs
from repro.sharding.specs import LogicalRules, shard_as, shard_as_bf16_grad

ZERO_METRICS = {"moe_aux": 0.0, "moe_dropped": 0.0}
#: the dropless MoE layer's per-expert row counts, int32 (E,) a layer
EXPERT_ROWS = "expert_rows"
#: a MoE layer's expert matrices, (E, d, f) each: the leaves a paged decode
#: step reads in place from the stage's stack
EXPERT_MATRICES = ("wi_gate", "wi_up", "wo")


def _metrics_like(m: dict | None) -> dict:
    out = dict(ZERO_METRICS)
    if m:
        out.update(m)
    return {k: v if k == EXPERT_ROWS else jnp.asarray(v, jnp.float32) for k, v in out.items()}


def _stack_metrics(metrics: dict, keep_rows: bool = False) -> dict:
    """A stack's metrics from its layers' (leading layer axis): the scalars
    summed; the expert row counts kept per layer, (L, E), or dropped."""
    out = {k: jnp.sum(v) for k, v in metrics.items() if k != EXPERT_ROWS}
    if keep_rows and EXPERT_ROWS in metrics:
        out[EXPERT_ROWS] = metrics[EXPERT_ROWS]
    return out


def _ffn(params, h: jax.Array, cfg: ModelConfig, kind: str, rules, valid=None, expert_layer=None):
    if kind == "moe":
        return moe_mod.apply_moe(params["moe"], h, cfg, rules, valid, expert_layer)
    return apply_mlp(params["mlp"], h, cfg), None


# ------------------------------------------------------------------ blocks


def block_defs(cfg: ModelConfig, kind: str):
    """kind: dense | moe | ssm"""
    if kind == "ssm":
        return {"ln1": norm_defs(cfg), "ssm": ssm_mod.ssm_defs(cfg)}
    defs = {
        "ln1": norm_defs(cfg),
        "attn": attn_mod.attn_defs(cfg),
        "ln2": norm_defs(cfg),
    }
    if kind == "moe":
        defs["moe"] = moe_mod.moe_defs(cfg)
    else:
        defs["mlp"] = mlp_defs(cfg)
    return defs


def layer_kind(cfg: ModelConfig) -> str:
    if cfg.family == "ssm":
        return "ssm"
    if cfg.family == "moe":
        return "moe"
    return "dense"


def apply_block_full(
    params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    positions: jax.Array,
    causal: bool = True,
    collect_cache: bool = False,
    layer=None,
):
    """Full-sequence block. Returns (x, cache_entry | None, metrics).

    cache_entry: (k, v) for attention kinds, ssm state dict for 'ssm'.
    ``layer`` (the model index, an int or a traced int32) chooses the
    layer's window and RoPE."""
    metrics = None
    if kind == "ssm":
        h = apply_norm(params["ln1"], x, cfg)
        if collect_cache:
            out, cache = ssm_mod.apply_ssm(params["ssm"], h, cfg, rules, return_cache=True)
        else:
            out, cache = ssm_mod.apply_ssm(params["ssm"], h, cfg, rules), None
        x = x + out
        x = shard_as(x, ("batch", "seq", None), rules)
        return x, cache, _metrics_like(metrics)

    h = apply_norm(params["ln1"], x, cfg)
    q, k, v = attn_mod.qkv_project(params["attn"], h, cfg, positions, layer)
    q = shard_as(q, ("batch", "seq_full", "act_heads", None), rules)
    k_attn, v_attn = k, v
    if rules is not None:
        msize = rules.mesh_axis_sizes.get("model", 1)
        if cfg.num_heads % msize == 0 and 1 < cfg.num_kv_heads < msize:
            # GQA under TP: the (kv, group) split of the head dim cannot be
            # sharded 16-way without GSPMD splitting BOTH sub-dims, which
            # inserts partial-sum all-reduces inside every attention chunk
            # (measured ~360 GB/step on qwen3 train — EXPERIMENTS §Perf #3).
            # K/V are TP-replicated anyway; repeating them to full heads
            # keeps the head dim cleanly sharded and attention collective-free.
            rep = cfg.num_heads // cfg.num_kv_heads
            k_attn = jnp.repeat(k, rep, axis=2)
            v_attn = jnp.repeat(v, rep, axis=2)
            k_attn = shard_as(k_attn, ("batch", "seq_full", "act_heads", None), rules)
            v_attn = shard_as(v_attn, ("batch", "seq_full", "act_heads", None), rules)
    out = attn_mod.full_attention(q, k_attn, v_attn, causal=causal,
                                  window=attn_mod.layer_window(cfg, layer))
    x = x + attn_mod.attn_output(params["attn"], out)
    x = shard_as_bf16_grad(x, ("batch", "seq", None), rules)
    if collect_cache:
        # the prefill-built cache must land in the decode layout (seq or
        # kv-heads over 'model'), not batch-only sharded — and in the
        # configured cache dtype (fp8 when quantized-KV is on)
        cache_dt = jnp.dtype(cfg.kv_cache_dtype)
        k = shard_as(k.astype(cache_dt), ("batch", "cache_seq", "cache_kv_heads", None), rules)
        v = shard_as(v.astype(cache_dt), ("batch", "cache_seq", "cache_kv_heads", None), rules)

    h = apply_norm(params["ln2"], x, cfg)
    y, metrics = _ffn(params, h, cfg, kind, rules)
    x = x + y
    x = shard_as_bf16_grad(x, ("batch", "seq", None), rules)
    return x, (k, v), _metrics_like(metrics)


def apply_block_decode(
    params,
    x: jax.Array,
    cache: dict,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    cur_len: jax.Array,
    layer=None,
):
    """Single-token block step. cache: {'k','v'} or SSM state dict."""
    metrics = None
    if kind == "ssm":
        h = apply_norm(params["ln1"], x, cfg)
        out, new_cache = ssm_mod.ssm_decode_step(params["ssm"], h, cache, cfg)
        return x + out, new_cache, _metrics_like(metrics)

    positions = cur_len[:, None]  # (B, 1)
    h = apply_norm(params["ln1"], x, cfg)
    q, k_new, v_new = attn_mod.qkv_project(params["attn"], h, cfg, positions, layer)
    k_cache, v_cache = attn_mod.update_kv_cache(cache["k"], cache["v"], k_new, v_new, positions)
    out = attn_mod.decode_attention(q, k_cache, v_cache, cur_len + 1,
                                    attn_mod.layer_window(cfg, layer))
    x = x + attn_mod.attn_output(params["attn"], out)

    h = apply_norm(params["ln2"], x, cfg)
    y, metrics = _ffn(params, h, cfg, kind, rules)
    x = x + y
    new_cache = {"k": k_cache, "v": v_cache}
    return x, new_cache, _metrics_like(metrics)


def apply_block_decode_paged(
    params,
    x: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    cur_len: jax.Array,
    write_kv: bool = True,
    layer=None,
    expert_layer=None,
):
    """Single-token block step against one layer's page arena slice.

    Same math as :func:`apply_block_decode` but the KV cache is
    ``(num_pages, KV, page, hd)`` shared across requests, addressed through
    the batch's block table: scatter the new token's K/V into its page,
    then attend through the table (kernel indirection on TPU, contiguous
    gather elsewhere). Attention kinds only — SSM state is recurrent, not
    length-indexed, so it has no pages.

    ``write_kv=False`` runs a FROZEN step: the new token's K/V is assumed
    already resident at position ``cur_len`` (a shared-prefix-cache hit)
    and nothing is written — the engine uses this to recover first-token
    logits for a whole-prompt hit without touching shared pages.

    ``layer`` (the model index) chooses the window and the RoPE. An empty
    slot (its block-table row all the arena's scratch page 0) routes to no
    expert. ``expert_layer`` (the index in the stage's stack) says the MoE
    params carry the stack's expert matrices, (L, E, d, f), read in place."""
    if kind == "ssm":
        raise ValueError("paged decode applies to attention caches only")
    positions = cur_len[:, None]  # (B, 1)
    h = apply_norm(params["ln1"], x, cfg)
    q, k_new, v_new = attn_mod.qkv_project(params["attn"], h, cfg, positions, layer)
    if write_kv:
        k_pages, v_pages = attn_mod.update_paged_kv(
            k_pages, v_pages, k_new, v_new, block_table, cur_len
        )
    out = attn_mod.paged_decode_attention(q, k_pages, v_pages, block_table, cur_len + 1,
                                          attn_mod.layer_window(cfg, layer))
    x = x + attn_mod.attn_output(params["attn"], out)

    h = apply_norm(params["ln2"], x, cfg)
    y, metrics = _ffn(params, h, cfg, kind, rules, valid=(block_table[:, :1] != 0),
                      expert_layer=expert_layer)
    x = x + y
    return x, k_pages, v_pages, _metrics_like(metrics)


def apply_block_prefill_chunk_paged(
    params,
    x: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    start: jax.Array,
    valid: jax.Array,
    layer=None,
):
    """One prefill CHUNK's block step against a layer's page arena slice.

    ``x``: (1, C, d) — C chunk rows whose absolute positions begin at
    ``start`` (shape (1,)); ``valid`` (shape (1,)) counts the real rows
    (the rest are compile-cache padding whose K/V writes route to the
    scratch page). The chunk's K/V is scattered BEFORE attention so chunk
    tokens attend to themselves and each other, exactly like the matching
    rows of a dense causal prefill — chunked prefill is bit-exact vs dense
    on the gather path. Padding rows route to no expert."""
    if kind == "ssm":
        raise ValueError("paged prefill applies to attention caches only")
    c = x.shape[1]
    positions = start[:, None] + jnp.arange(c)[None, :]  # (1, C)
    h = apply_norm(params["ln1"], x, cfg)
    q, k_new, v_new = attn_mod.qkv_project(params["attn"], h, cfg, positions, layer)
    k_pages, v_pages = attn_mod.update_paged_kv_chunk(
        k_pages, v_pages, k_new, v_new, block_table, start, valid
    )
    out = attn_mod.paged_chunk_attention(q, k_pages, v_pages, block_table, start,
                                         attn_mod.layer_window(cfg, layer))
    x = x + attn_mod.attn_output(params["attn"], out)

    h = apply_norm(params["ln2"], x, cfg)
    y, metrics = _ffn(params, h, cfg, kind, rules, valid=(jnp.arange(c) < valid[0])[None, :])
    x = x + y
    return x, k_pages, v_pages, _metrics_like(metrics)


# ------------------------------------------------------------------ stacks


def stack_block_defs(cfg: ModelConfig, kind: str, n_layers: int):
    return stack_defs(block_defs(cfg, kind), n_layers)


def apply_stack_full(
    stacked_params,
    x: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    positions: jax.Array,
    causal: bool = True,
    collect_cache: bool = False,
    unroll: bool | None = None,
    first_layer: int = 0,
):
    """Full-sequence pass through the stack.

    Returns (x, stacked cache pytree (leading 'layers' dim) or None, metrics
    summed). For attention kinds the cache is {'k','v'}; for ssm it is the
    ssm state dict.

    ``unroll`` exists for experimentation; the scan path is the default for
    all passes (unrolled loops lose cross-layer buffer reuse).
    ``first_layer`` is the model index of the stack's first layer."""
    if unroll is None:
        unroll = False
    n = jax.tree.leaves(stacked_params)[0].shape[0]
    if unroll:
        entries = []
        metrics = None
        for i in range(n):
            lp = jax.tree.map(lambda a: a[i], stacked_params)
            x, entry, m = apply_block_full(lp, x, cfg, kind, rules, positions, causal,
                                           collect_cache, first_layer + i)
            m.pop(EXPERT_ROWS, None)
            metrics = m if metrics is None else jax.tree.map(jnp.add, metrics, m)
            if collect_cache:
                entries.append(entry)
        cache = None
        if collect_cache and entries and entries[0] is not None:
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *entries)
            cache = {"k": stacked[0], "v": stacked[1]} if isinstance(stacked, tuple) else stacked
        return x, cache, metrics

    def body(carry, inp):
        layer, layer_params = inp
        h, entry, metrics = apply_block_full(
            layer_params, carry, cfg, kind, rules, positions, causal, collect_cache, layer
        )
        ys = (entry if collect_cache else None, metrics)
        return h, ys

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, (entries, metrics) = jax.lax.scan(body_fn, x, (first_layer + jnp.arange(n), stacked_params))
    cache = None
    if collect_cache and entries is not None:
        cache = {"k": entries[0], "v": entries[1]} if isinstance(entries, tuple) else entries
    return x, cache, _stack_metrics(metrics)


def apply_stack_decode(
    stacked_params,
    x: jax.Array,
    caches,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    cur_len: jax.Array,
    mode: str = "carry",
    first_layer: int = 0,
):
    """One decode step through the stack; caches have a leading 'layers' dim.

    mode='carry' (default): the cache rides in the scan CARRY and each layer
    does an in-place dynamic-update at its index — ONE cache buffer total.
    Passing the cache as scan xs/ys instead makes XLA double-buffer it
    (in + out copies; measured +2x cache temp on the 34B decode cells), and
    a python-unrolled loop is worse still (no cross-layer buffer reuse).
    mode='xs' keeps the plain xs/ys formulation for comparison."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]
    layers = first_layer + jnp.arange(n)
    if mode == "carry":
        def body(carry, inp):
            i, layer_params = inp
            h, caches_c = carry
            cache_i = jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, 0, keepdims=False), caches_c)
            h, new_cache, metrics = apply_block_decode(layer_params, h, cache_i, cfg, kind, rules,
                                                       cur_len, first_layer + i)
            caches_c = jax.tree.map(
                lambda a, u: jax.lax.dynamic_update_index_in_dim(a, u.astype(a.dtype), i, 0),
                caches_c, new_cache,
            )
            return (h, caches_c), metrics

        (x, new_caches), metrics = jax.lax.scan(
            body, (x, caches), (jnp.arange(n), stacked_params)
        )
        return x, new_caches, _stack_metrics(metrics)

    def body(carry, inp):
        layer, layer_params, cache = inp
        h, new_cache, metrics = apply_block_decode(layer_params, carry, cache, cfg, kind, rules,
                                                   cur_len, layer)
        return h, (new_cache, metrics)

    x, (new_caches, metrics) = jax.lax.scan(body, x, (layers, stacked_params, caches))
    return x, new_caches, _stack_metrics(metrics)


def apply_stack_decode_paged(
    stacked_params,
    x: jax.Array,
    arena: dict,
    block_table: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    cur_len: jax.Array,
    write_kv: bool = True,
    first_layer: int = 0,
):
    """One decode step through the stack against a paged arena.

    ``arena``: ``{'k','v'}`` of shape (L, num_pages, KV, page, hd) — the
    stage's slice of the shared pool. Like :func:`apply_stack_decode`'s
    carry mode, the arena rides in the scan CARRY with per-layer in-place
    dynamic updates, so the whole pool stays ONE buffer through the stack
    instead of double-buffering per layer. ``write_kv=False`` is the frozen
    step (see :func:`apply_block_decode_paged`): nothing is scattered and
    the arena comes back unchanged. Layer ``i`` of the stack is the model's
    layer ``first_layer + i``, which sets its window and RoPE; the metrics
    keep a MoE stack's expert row counts per layer, (L, E).

    On one device (``rules is None``) a MoE stack's expert matrices stay
    whole, out of the scan's per-layer slices, and each layer's grouped
    matmuls read its experts in place at ``i``: a slice of a (E, d, f) bank
    that feeds a kernel is a copy of all E experts, where the step touches
    a few."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]
    per_layer, experts = stacked_params, None
    if rules is None and "moe" in stacked_params:
        moe = stacked_params["moe"]
        experts = {k: moe[k] for k in EXPERT_MATRICES}
        per_layer = dict(stacked_params, moe={k: v for k, v in moe.items() if k not in experts})

    def body(carry, inp):
        i, layer_params = inp
        h, arena_c = carry
        if experts is not None:
            layer_params = dict(layer_params, moe=dict(layer_params["moe"], **experts))
        k_pages = jax.lax.dynamic_index_in_dim(arena_c["k"], i, 0, keepdims=False)
        v_pages = jax.lax.dynamic_index_in_dim(arena_c["v"], i, 0, keepdims=False)
        h, k_pages, v_pages, metrics = apply_block_decode_paged(
            layer_params, h, k_pages, v_pages, block_table, cfg, kind, rules,
            cur_len, write_kv, first_layer + i, None if experts is None else i,
        )
        if write_kv:
            arena_c = {
                "k": jax.lax.dynamic_update_index_in_dim(
                    arena_c["k"], k_pages.astype(arena_c["k"].dtype), i, 0
                ),
                "v": jax.lax.dynamic_update_index_in_dim(
                    arena_c["v"], v_pages.astype(arena_c["v"].dtype), i, 0
                ),
            }
        return (h, arena_c), metrics

    (x, new_arena), metrics = jax.lax.scan(
        body, (x, arena), (jnp.arange(n), per_layer)
    )
    return x, new_arena, _stack_metrics(metrics, keep_rows=True)


def apply_stack_prefill_chunk_paged(
    stacked_params,
    x: jax.Array,
    arena: dict,
    block_table: jax.Array,
    cfg: ModelConfig,
    kind: str,
    rules: LogicalRules | None,
    start: jax.Array,
    valid: jax.Array,
    first_layer: int = 0,
):
    """One prefill chunk through the stack against a paged arena — same
    single-buffer carry pattern as :func:`apply_stack_decode_paged`, with
    the chunk block step (scatter C rows, attend causally from ``start``)
    in place of the single-token one."""
    n = jax.tree.leaves(stacked_params)[0].shape[0]

    def body(carry, inp):
        i, layer_params = inp
        h, arena_c = carry
        k_pages = jax.lax.dynamic_index_in_dim(arena_c["k"], i, 0, keepdims=False)
        v_pages = jax.lax.dynamic_index_in_dim(arena_c["v"], i, 0, keepdims=False)
        h, k_pages, v_pages, metrics = apply_block_prefill_chunk_paged(
            layer_params, h, k_pages, v_pages, block_table, cfg, kind, rules,
            start, valid, first_layer + i,
        )
        arena_c = {
            "k": jax.lax.dynamic_update_index_in_dim(
                arena_c["k"], k_pages.astype(arena_c["k"].dtype), i, 0
            ),
            "v": jax.lax.dynamic_update_index_in_dim(
                arena_c["v"], v_pages.astype(arena_c["v"].dtype), i, 0
            ),
        }
        return (h, arena_c), metrics

    (x, new_arena), metrics = jax.lax.scan(
        body, (x, arena), (jnp.arange(n), stacked_params)
    )
    return x, new_arena, _stack_metrics(metrics, keep_rows=True)
