"""Shared neural-net layers: norms, rotary embedding, MLPs, embeddings.

Pure-functional style: ``*_defs(cfg)`` returns a ParamDef tree, ``fn(params,
x, ...)`` applies it. Compute is bf16 with fp32 accumulation in norms,
softmax and the loss.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef

# ---------------------------------------------------------------- norms


def norm_defs(cfg: ModelConfig, d: int | None = None):
    d = d or cfg.d_model
    if cfg.norm == "rmsnorm":
        return {"scale": ParamDef((d,), ("embed",), init="ones")}
    return {
        "scale": ParamDef((d,), ("embed",), init="ones"),
        "bias": ParamDef((d,), ("embed",), init="zeros"),
    }


def apply_norm(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    eps = cfg.norm_eps
    xf = x.astype(jnp.float32)
    if "bias" in params:  # LayerNorm
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:  # RMSNorm
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + eps) * params["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rms_norm_1d(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """Headwise RMSNorm (QK-norm): normalizes the trailing dim."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- rotary


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta**exponent)  # (head_dim/2,)


def yarn_freqs(cfg: ModelConfig, head_dim: int) -> jax.Array:
    """YaRN's inverse frequencies (HF ``_compute_yarn_parameters``): each
    dimension blends the plain frequency (extrapolation) and the plain
    frequency over ``yarn_factor`` (interpolation) by a linear ramp between
    the dimensions that turn ``yarn_beta_fast`` and ``yarn_beta_slow``
    times over the original context."""
    theta, orig = cfg.rope_theta, cfg.yarn_original_max_position

    def dim_of(rotations: float) -> float:
        return head_dim * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(dim_of(cfg.yarn_beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0.0, 1.0)
    plain = rope_freqs(head_dim, theta)
    return plain / cfg.yarn_factor * ramp + plain * (1.0 - ramp)


def layer_rope(cfg: ModelConfig, head_dim: int, layer=None) -> tuple[jax.Array, jax.Array | float]:
    """(inverse frequencies, cos/sin scale) of layer ``layer`` (an int or a
    traced int32): YaRN's on a full layer of a configuration with YaRN,
    plain RoPE at ``rope_theta`` otherwise."""
    plain = rope_freqs(head_dim, cfg.rope_theta)
    if not cfg.yarn_factor:
        return plain, 1.0
    if layer is None:
        raise ValueError(f"{cfg.name}: YaRN applies by layer, and no layer index was given")
    full = cfg.full_layer(layer)
    return (jnp.where(full, yarn_freqs(cfg, head_dim), plain),
            jnp.where(full, cfg.yarn_attention_factor, 1.0))


def apply_rope(x: jax.Array, positions: jax.Array, freqs: jax.Array, scale=1.0) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: broadcastable to (..., seq);
    ``freqs`` (head_dim/2,) and ``scale`` from :func:`layer_rope`: cos and
    sin are multiplied by ``scale``."""
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = (jnp.cos(angles) * scale)[..., :, None, :]  # (..., seq, 1, hd/2)
    sin = (jnp.sin(angles) * scale)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------- mlp


def mlp_defs(cfg: ModelConfig, d_ff: int | None = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.act == "silu":  # SwiGLU: gate + up + down
        return {
            "wi_gate": ParamDef((d, f), ("embed_fsdp", "ff")),
            "wi_up": ParamDef((d, f), ("embed_fsdp", "ff")),
            "wo": ParamDef((f, d), ("ff", "embed_fsdp")),
        }
    return {
        "wi": ParamDef((d, f), ("embed_fsdp", "ff")),
        "wo": ParamDef((f, d), ("ff", "embed_fsdp")),
    }


def apply_mlp(params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if "wi_gate" in params:
        g = jnp.einsum("...d,df->...f", x, params["wi_gate"])
        u = jnp.einsum("...d,df->...f", x, params["wi_up"])
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:
        h = jnp.einsum("...d,df->...f", x, params["wi"])
        h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    return jnp.einsum("...f,fd->...d", h, params["wo"])


# ---------------------------------------------------------------- embeddings


def embedding_defs(cfg: ModelConfig):
    defs = {"table": ParamDef((cfg.vocab_size, cfg.d_model), ("vocab", "embed_fsdp"), init="embed")}
    if not cfg.tie_embeddings:
        defs["head"] = ParamDef((cfg.d_model, cfg.vocab_size), ("embed_fsdp", "vocab"))
    return defs


def embed_tokens(params, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["table"], tokens, axis=0)


def unembed(params, x: jax.Array) -> jax.Array:
    """Returns fp32 logits (vocab sharded over 'model' via the head kernel)."""
    if "head" in params:
        return jnp.einsum("...d,dv->...v", x, params["head"]).astype(jnp.float32)
    return jnp.einsum("...d,vd->...v", x, params["table"]).astype(jnp.float32)
