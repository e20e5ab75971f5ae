"""Encoder-decoder transformer (SeamlessM4T-style backbone).

The modality frontend is a STUB per the assignment: `input_specs()` provides
precomputed frame embeddings (B, S_src, d) — the encoder consumes them
directly. Decoder = causal self-attention + cross-attention over encoder
states.

On the Provuse platform the encoder and decoder are deployed as two separate
functions — the decoder's blocking wait on encoder output is the canonical
synchronous edge the Function Handler detects (DESIGN.md §4).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn_mod
from repro.models import transformer as tfm
from repro.models.layers import apply_mlp, apply_norm, mlp_defs, norm_defs
from repro.models.params import ParamDef, stack_defs
from repro.sharding.specs import LogicalRules, shard_as


def cross_attn_defs(cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        # fan-in is d for the projections in and h*hd for the one out: a
        # (d, h, hd) leaf's default axis (-2) would be h, giving q/k entries
        # of std ~sqrt(d/h) and a saturated, chaotic softmax at init
        "wq": ParamDef((d, h, hd), ("embed_fsdp", "heads", "head_dim"), scale_axis=-3),
        "wk": ParamDef((d, kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), scale_axis=-3),
        "wv": ParamDef((d, kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), scale_axis=-3),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed_fsdp"), scale_axis=(-3, -2)),
    }


def decoder_block_defs(cfg: ModelConfig):
    defs = tfm.block_defs(cfg, "dense")
    defs["ln_cross"] = norm_defs(cfg)
    defs["cross"] = cross_attn_defs(cfg)
    return defs


def encdec_defs(cfg: ModelConfig):
    return {
        "encoder": stack_defs(tfm.block_defs(cfg, "dense"), cfg.num_layers),
        "decoder": stack_defs(decoder_block_defs(cfg), cfg.num_decoder_layers),
    }


def _apply_cross(params, x, enc_kv, cfg, valid_src_len=None):
    """x: (B,T,d); enc_kv = (k,v): (B,S,KV,hd)."""
    h = apply_norm(params["ln_cross"], x, cfg)
    q = jnp.einsum("btd,dhk->bthk", h, params["cross"]["wq"])
    if x.shape[1] == 1 and valid_src_len is not None:
        out = attn_mod.decode_attention(q, enc_kv[0], enc_kv[1], valid_src_len)
    else:
        out = attn_mod.full_attention(q, enc_kv[0], enc_kv[1], causal=False)
    return x + jnp.einsum("bthk,hkd->btd", out, params["cross"]["wo"])


def encode(params, src: jax.Array, cfg: ModelConfig, rules: LogicalRules | None):
    """src: (B, S, d) frame embeddings -> encoder states (B, S, d)."""
    positions = jnp.arange(src.shape[1])[None, :]
    x, _, metrics = tfm.apply_stack_full(
        params["encoder"], src, cfg, "dense", rules, positions, causal=False
    )
    return x, metrics


def cross_kv_from_enc(params, enc: jax.Array):
    """Project encoder states into per-decoder-layer cross K/V.
    Returns {'k','v'}: (L_dec, B, S, KV, hd) — the decode-time cross cache."""

    def one_layer(layer_params, _):
        k = jnp.einsum("bsd,dhk->bshk", enc, layer_params["cross"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc, layer_params["cross"]["wv"])
        return None, (k, v)

    _, (ks, vs) = jax.lax.scan(lambda c, p: one_layer(p, c), None, params["decoder"])
    return {"k": ks, "v": vs}


def decode_train(params, tgt_emb: jax.Array, enc: jax.Array, cfg: ModelConfig, rules):
    """Teacher-forced decoder over full target. tgt_emb: (B, T, d)."""
    positions = jnp.arange(tgt_emb.shape[1])[None, :]

    def body(carry, layer_params):
        h, _, metrics = tfm.apply_block_full(layer_params, carry, cfg, "dense", rules, positions, causal=True)
        k = jnp.einsum("bsd,dhk->bshk", enc, layer_params["cross"]["wk"])
        v = jnp.einsum("bsd,dhk->bshk", enc, layer_params["cross"]["wv"])
        h = _apply_cross(layer_params, h, (k, v), cfg)
        h = shard_as(h, ("batch", "seq", None), rules)
        return h, metrics

    body_fn = jax.checkpoint(body) if cfg.remat else body
    x, metrics = jax.lax.scan(body_fn, tgt_emb, params["decoder"])
    return x, jax.tree.map(jnp.sum, metrics)


def decoder_step(params, x: jax.Array, self_cache, cross_cache, cfg, rules, cur_len, src_len):
    """One decode token. self_cache k/v: (L,B,S_tgt,KV,hd); cross_cache k/v:
    (L,B,S_src,KV,hd)."""

    def body(carry, inp):
        layer_params, cache, ck, cv = inp
        h, new_cache, metrics = tfm.apply_block_decode(layer_params, carry, cache, cfg, "dense", rules, cur_len)
        h = _apply_cross(layer_params, h, (ck, cv), cfg, valid_src_len=src_len)
        return h, (new_cache, metrics)

    x, (new_caches, metrics) = jax.lax.scan(
        body, x, (params["decoder"], self_cache, cross_cache["k"], cross_cache["v"])
    )
    return x, new_caches, jax.tree.map(jnp.sum, metrics)
