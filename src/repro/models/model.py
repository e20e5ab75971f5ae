"""build_model(cfg, rules) — uniform Model API over all 10 arch families.

A Model exposes three *programs* (pure functions of pytrees — exactly what
the Provuse platform deploys as FaaS functions and what the dry-run lowers):

  loss_fn(params, batch)            -> (loss, metrics)          [train]
  prefill_fn(params, batch)         -> (last_logits, cache)     [serve]
  decode_fn(params, batch, cache)   -> (logits, new_cache)      [serve]

plus symbolic builders (``param_defs`` / ``cache_defs`` / ``input_defs``) so
dry-runs construct sharded ShapeDtypeStructs without allocating anything.

Modality frontends (audio frames / VQ image patches) are STUBS per the
assignment: ``input_defs`` provides precomputed embeddings for those archs.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import encdec as ed
from repro.models import hybrid as hy
from repro.models import ssm as ssm_mod
from repro.models import transformer as tfm
from repro.models.layers import embed_tokens, embedding_defs, norm_defs, apply_norm, unembed
from repro.models.params import ParamDef, init_params
from repro.sharding.specs import LogicalRules, shard_as

ENCDEC_TGT_CACHE = 4096  # decoder self-cache length for enc-dec decode cells
CE_CHUNK = 512


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    param_defs: Any
    init: Callable[[jax.Array], Any]
    loss_fn: Callable
    prefill_fn: Callable
    decode_fn: Callable
    cache_defs: Callable[[ShapeConfig], Any]
    input_defs: Callable[[ShapeConfig], Any]
    make_inputs: Callable[[ShapeConfig, jax.Array], Any]


# ------------------------------------------------------------------ loss


def chunked_ce(emb_params, hidden: jax.Array, targets: jax.Array, cfg: ModelConfig, rules, chunk: int = CE_CHUNK):
    """Cross-entropy via scan over sequence chunks: the (B, chunk, V) logits
    buffer replaces the (B, T, V) one — the full-vocab logits tensor for
    train_4k would otherwise be the largest buffer in the program."""
    b, t, _ = hidden.shape
    c = min(chunk, t)
    if t % c:
        c = t
    nc = t // c
    hc = jnp.moveaxis(hidden.reshape(b, nc, c, -1), 1, 0)
    tc = jnp.moveaxis(targets.reshape(b, nc, c), 1, 0)

    def body(tot, inp):
        h, y = inp
        logits = unembed(emb_params, h)  # (B, c, V) fp32
        logits = shard_as(logits, ("batch", None, "vocab_out"), rules)
        lz = jax.scipy.special.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, y[..., None], axis=-1)[..., 0]
        return tot + jnp.sum(lz - ll), None

    body_fn = jax.checkpoint(body) if cfg.remat else body
    tot, _ = jax.lax.scan(body_fn, jnp.zeros((), jnp.float32), (hc, tc))
    return tot / (b * t)


# ------------------------------------------------------------------ builder


def build_model(cfg: ModelConfig, rules: LogicalRules | None = None, *, layout: str = "stacked") -> Model:
    """layout='stacked' (default): layer params stacked on a leading axis,
    applied with lax.scan — O(1) HLO, the right shape for training (remat,
    FSDP gathers amortize).

    layout='perlayer': every layer is a separate pytree subtree and the
    forward is a python loop — the right shape for SERVING programs: no
    stacked-xs double-buffering and no param/cache slice copies (measured
    ~0.36 GB/layer of dead temp on the 34B decode cells otherwise), and each
    layer's cache leaf aliases its donated input in place. Only affects the
    blocks families (dense/moe/vlm/ssm); hybrid/enc-dec keep their layouts.
    """
    fam = cfg.family
    L = cfg.num_layers
    blk_kind = "moe" if fam == "moe" else ("ssm" if fam == "ssm" else "dense")
    perlayer = layout == "perlayer" and fam in ("dense", "moe", "vlm", "ssm")

    # ---------------- param defs ----------------
    defs: dict = {"embed": embedding_defs(cfg), "ln_f": norm_defs(cfg)}
    if fam in ("dense", "moe", "vlm", "ssm"):
        if perlayer:
            defs["blocks"] = {f"l{i:03d}": tfm.block_defs(cfg, blk_kind) for i in range(L)}
        else:
            defs["blocks"] = tfm.stack_block_defs(cfg, blk_kind, L)
    elif fam == "hybrid":
        defs["hybrid"] = hy.hybrid_defs(cfg)
    elif fam == "audio":
        defs["encdec"] = ed.encdec_defs(cfg)
    else:
        raise ValueError(f"unknown family {fam}")

    # ---------------- forward helpers ----------------
    def _in_embeds(params, batch):
        if "embeds" in batch:
            return batch["embeds"]
        return embed_tokens(params["embed"], batch["tokens"])

    def _hidden_full(params, x, collect_cache: bool):
        positions = jnp.arange(x.shape[1])[None, :]
        if fam in ("dense", "moe", "vlm", "ssm"):
            if perlayer:
                h = x
                cache = {} if collect_cache else None
                metrics = None
                for i, key in enumerate(sorted(params["blocks"])):
                    h, entry, m = tfm.apply_block_full(
                        params["blocks"][key], h, cfg, blk_kind, rules, positions,
                        causal=True, collect_cache=collect_cache, layer=i,
                    )
                    m.pop(tfm.EXPERT_ROWS, None)
                    metrics = m if metrics is None else jax.tree.map(jnp.add, metrics, m)
                    if collect_cache:
                        cache[key] = {"k": entry[0], "v": entry[1]} if isinstance(entry, tuple) else entry
            else:
                h, cache, metrics = tfm.apply_stack_full(
                    params["blocks"], x, cfg, blk_kind, rules, positions,
                    causal=True, collect_cache=collect_cache,
                )
        elif fam == "hybrid":
            h, cache, metrics = hy.apply_hybrid_full(
                params["hybrid"], x, cfg, rules, positions, collect_cache=collect_cache
            )
        else:
            raise AssertionError(fam)
        return apply_norm(params["ln_f"], h, cfg), cache, metrics

    # ---------------- train ----------------
    def loss_fn(params, batch):
        if fam == "audio":
            enc, m1 = ed.encode(params["encdec"], batch["src_embeds"], cfg, rules)
            tgt = embed_tokens(params["embed"], batch["tgt_tokens"])
            h, m2 = ed.decode_train(params["encdec"], tgt, enc, cfg, rules)
            metrics = jax.tree.map(jnp.add, m1, m2)
            h = apply_norm(params["ln_f"], h, cfg)
        else:
            x = _in_embeds(params, batch)
            h, _, metrics = _hidden_full(params, x, collect_cache=False)
        ce = chunked_ce(params["embed"], h, batch["targets"], cfg, rules)
        loss = ce + cfg.router_aux_weight * metrics["moe_aux"]
        out = dict(metrics)
        out.update(ce=ce, loss=loss)
        return loss, out

    # ---------------- serve: prefill ----------------
    def prefill_fn(params, batch):
        if fam == "audio":
            enc, _ = ed.encode(params["encdec"], batch["src_embeds"], cfg, rules)
            cross = ed.cross_kv_from_enc(params["encdec"], enc)
            b = enc.shape[0]
            kvh, hd = cfg.num_kv_heads, cfg.head_dim
            self_cache = {
                "k": jnp.zeros((cfg.num_decoder_layers, b, ENCDEC_TGT_CACHE, kvh, hd), jnp.bfloat16),
                "v": jnp.zeros((cfg.num_decoder_layers, b, ENCDEC_TGT_CACHE, kvh, hd), jnp.bfloat16),
            }
            x = embed_tokens(params["embed"], batch["tokens"])  # BOS (B, 1)
            cur = jnp.zeros((b,), jnp.int32)
            src_len = jnp.full((b,), enc.shape[1], jnp.int32)
            h, new_self, _ = ed.decoder_step(params["encdec"], x, self_cache, cross, cfg, rules, cur, src_len)
            h = apply_norm(params["ln_f"], h, cfg)
            logits = unembed(params["embed"], h)[:, 0]
            return logits, {"self": new_self, "cross": cross}
        x = _in_embeds(params, batch)
        h, cache, _ = _hidden_full(params, x, collect_cache=True)
        logits = unembed(params["embed"], h[:, -1:])[:, 0]  # last position only
        logits = shard_as(logits, ("batch", "vocab_out"), rules)
        return logits, cache

    # ---------------- serve: decode ----------------
    def decode_fn(params, batch, cache):
        cur_len = batch["cur_len"]
        x = embed_tokens(params["embed"], batch["tokens"])  # (B, 1, d)
        if fam in ("dense", "moe", "vlm", "ssm"):
            if perlayer:
                h = x
                new_cache = {}
                for i, key in enumerate(sorted(params["blocks"])):
                    h, nc, _ = tfm.apply_block_decode(
                        params["blocks"][key], h, cache[key], cfg, blk_kind, rules, cur_len, i
                    )
                    new_cache[key] = nc
            else:
                h, new_cache, _ = tfm.apply_stack_decode(
                    params["blocks"], x, cache, cfg, blk_kind, rules, cur_len
                )
        elif fam == "hybrid":
            h, new_cache, _ = hy.apply_hybrid_decode(params["hybrid"], x, cache, cfg, rules, cur_len)
        elif fam == "audio":
            b = x.shape[0]
            src_len = jnp.full((b,), cache["cross"]["k"].shape[2], jnp.int32)
            h, new_self, _ = ed.decoder_step(
                params["encdec"], x, cache["self"], cache["cross"], cfg, rules, cur_len, src_len
            )
            new_cache = {"self": new_self, "cross": cache["cross"]}
        else:
            raise AssertionError(fam)
        h = apply_norm(params["ln_f"], h, cfg)
        logits = unembed(params["embed"], h)[:, 0]
        logits = shard_as(logits, ("batch", "vocab_out"), rules)
        return logits, new_cache

    # ---------------- symbolic cache / input defs ----------------
    def _attn_cache_defs(n_apps: int | None, batch: int, seq: int, lead: str = "layers"):
        """n_apps=None -> single-layer (perlayer layout) defs."""
        kvh, hd = cfg.num_kv_heads, cfg.head_dim
        cache_dt = jnp.dtype(cfg.kv_cache_dtype)
        if n_apps is None:
            sh: tuple = (batch, seq, kvh, hd)
            lg: tuple = ("batch", "cache_seq", "cache_kv_heads", "head_dim")
        else:
            sh = (n_apps, batch, seq, kvh, hd)
            lg = (lead, "batch", "cache_seq", "cache_kv_heads", "head_dim")
        return {
            "k": ParamDef(sh, lg, init="zeros", dtype=cache_dt),
            "v": ParamDef(sh, lg, init="zeros", dtype=cache_dt),
        }

    def _ssm_cache_defs(stack_dims: tuple[int, ...], stack_logical: tuple[str, ...], batch: int):
        shapes = ssm_mod.ssm_cache_shapes(cfg, batch)
        logical = {
            "ssd": ("batch", "ssm_heads", None, None),
            "conv_x": ("batch", "conv_k", "ssm_inner"),
            "conv_B": ("batch", "conv_k", None, "ssm_state"),
            "conv_C": ("batch", "conv_k", None, "ssm_state"),
        }
        return {
            name: ParamDef(stack_dims + sh, stack_logical + logical[name], init="zeros", dtype=dt)
            for name, (sh, dt) in shapes.items()
        }

    def cache_defs(shape: ShapeConfig):
        b, s = shape.global_batch, shape.seq_len
        if fam in ("dense", "moe", "vlm"):
            if perlayer:
                return {f"l{i:03d}": _attn_cache_defs(None, b, s) for i in range(L)}
            return _attn_cache_defs(L, b, s)
        if fam == "ssm":
            if perlayer:
                return {f"l{i:03d}": _ssm_cache_defs((), (), b) for i in range(L)}
            return _ssm_cache_defs((L,), ("layers",), b)
        if fam == "hybrid":
            n_groups, every, tail = hy.split_layers(cfg)
            out = {
                "groups": _ssm_cache_defs((n_groups, every), ("groups", "inner"), b),
                "attn": _attn_cache_defs(n_groups, b, s, lead="groups"),
            }
            if tail:
                out["tail"] = _ssm_cache_defs((tail,), ("inner",), b)
            return out
        if fam == "audio":
            return {
                "self": _attn_cache_defs(cfg.num_decoder_layers, b, min(ENCDEC_TGT_CACHE, s)),
                "cross": _attn_cache_defs(cfg.num_decoder_layers, b, s),
            }
        raise AssertionError(fam)

    def input_defs(shape: ShapeConfig):
        b, s, kind = shape.global_batch, shape.seq_len, shape.kind
        tok = lambda t: ParamDef((b, t), ("batch", "seq"), init="zeros", dtype=jnp.int32)
        emb = lambda t: ParamDef((b, t, cfg.d_model), ("batch", "seq", None), init="normal", dtype=jnp.bfloat16)
        if kind == "train":
            if fam == "audio":
                return {"src_embeds": emb(s), "tgt_tokens": tok(s), "targets": tok(s)}
            if fam == "vlm":
                return {"embeds": emb(s), "targets": tok(s)}
            return {"tokens": tok(s), "targets": tok(s)}
        if kind == "prefill":
            if fam == "audio":
                return {"src_embeds": emb(s), "tokens": ParamDef((b, 1), ("batch", None), init="zeros", dtype=jnp.int32)}
            if fam == "vlm":
                return {"embeds": emb(s)}
            return {"tokens": tok(s)}
        # decode: one new token against a cache of length s
        return {
            "tokens": ParamDef((b, 1), ("batch", None), init="zeros", dtype=jnp.int32),
            "cur_len": ParamDef((b,), ("batch",), init="zeros", dtype=jnp.int32),
        }

    def make_inputs(shape: ShapeConfig, rng: jax.Array):
        defs_in = input_defs(shape)
        keys = jax.random.split(rng, 8)
        out = {}
        for i, (name, d) in enumerate(sorted(defs_in.items())):
            if d.dtype == jnp.int32:
                if name == "cur_len":
                    out[name] = jnp.full(d.shape, max(0, shape.seq_len - 2), jnp.int32)
                else:
                    hi = max(2, cfg.vocab_size or 2)
                    out[name] = jax.random.randint(keys[i], d.shape, 0, hi, jnp.int32)
            else:
                out[name] = (jax.random.normal(keys[i], d.shape, jnp.float32) * 0.02).astype(d.dtype)
        return out

    return Model(
        cfg=cfg,
        param_defs=defs,
        init=lambda rng: init_params(defs, rng),
        loss_fn=loss_fn,
        prefill_fn=prefill_fn,
        decode_fn=decode_fn,
        cache_defs=cache_defs,
        input_defs=input_defs,
        make_inputs=make_inputs,
    )
