"""Mamba-2 (SSD — state-space duality) block. [arXiv:2405.21060]

Train/prefill uses the *chunked dual form*: intra-chunk attention-like
matmuls (MXU work) + an inter-chunk state recurrence carried by ``lax.scan``
— O(T * Q) compute/memory instead of O(T^2). Decode is the O(1) recurrent
step: state (B, H, P, N) update + readout; this is what makes `long_500k`
runnable for the SSM/hybrid archs.

TPU adaptation (DESIGN.md §2): chunk length defaults to 256 so the
intra-chunk (Q x Q) decay matrices and (Q x N/P) GEMMs are 128-multiple MXU
tiles; the inter-chunk recurrence stays as a scan (ICI-free, per-device).
The per-chunk core is also available as a Pallas kernel
(`repro.kernels.ssd_scan`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamDef
from repro.sharding.specs import LogicalRules, shard_as


def ssm_defs(cfg: ModelConfig):
    d, di = cfg.d_model, cfg.d_inner
    h, p, n, grp = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    return {
        "in_z": ParamDef((d, di), ("embed_fsdp", "ssm_inner")),
        "in_x": ParamDef((d, di), ("embed_fsdp", "ssm_inner")),
        "in_B": ParamDef((d, grp, n), ("embed_fsdp", None, "ssm_state")),
        "in_C": ParamDef((d, grp, n), ("embed_fsdp", None, "ssm_state")),
        "in_dt": ParamDef((d, h), ("embed_fsdp", "ssm_heads")),
        "conv_x": ParamDef((cfg.conv_kernel, di), ("conv_k", "ssm_inner")),
        "conv_B": ParamDef((cfg.conv_kernel, grp, n), ("conv_k", None, "ssm_state")),
        "conv_C": ParamDef((cfg.conv_kernel, grp, n), ("conv_k", None, "ssm_state")),
        "A_log": ParamDef((h,), ("ssm_heads",), init="zeros", dtype=jnp.float32),
        "D": ParamDef((h,), ("ssm_heads",), init="ones", dtype=jnp.float32),
        "dt_bias": ParamDef((h,), ("ssm_heads",), init="zeros", dtype=jnp.float32),
        "gate_norm": ParamDef((di,), ("ssm_inner",), init="ones"),
        "out": ParamDef((di, d), ("ssm_inner", "embed_fsdp")),
    }


def ssm_cache_shapes(cfg: ModelConfig, batch: int):
    """Decode-state shapes for ONE layer (stacked by the caller)."""
    return {
        "ssd": ((batch, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state), jnp.float32),
        "conv_x": ((batch, cfg.conv_kernel - 1, cfg.d_inner), jnp.bfloat16),
        "conv_B": ((batch, cfg.conv_kernel - 1, cfg.ssm_groups, cfg.ssm_state), jnp.bfloat16),
        "conv_C": ((batch, cfg.conv_kernel - 1, cfg.ssm_groups, cfg.ssm_state), jnp.bfloat16),
    }


def _causal_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """Depthwise causal conv along time. x: (B, T, C...), w: (K, C...)."""
    k = w.shape[0]
    orig = x.shape
    x2 = x.reshape(orig[0], orig[1], -1)
    w2 = w.reshape(k, -1)
    pad = jnp.zeros((orig[0], k - 1, x2.shape[-1]), x2.dtype)
    xp = jnp.concatenate([pad, x2], axis=1)
    out = sum(xp[:, i : i + orig[1]] * w2[i] for i in range(k))
    return out.reshape(orig)


def _project_inputs(params, u: jax.Array, cfg: ModelConfig):
    """u: (B, T, d) -> z, x, Bm, Cm, dt (pre-conv x/B/C; post-softplus dt)."""
    z = jnp.einsum("btd,de->bte", u, params["in_z"])
    x = jnp.einsum("btd,de->bte", u, params["in_x"])
    bm = jnp.einsum("btd,dgn->btgn", u, params["in_B"])
    cm = jnp.einsum("btd,dgn->btgn", u, params["in_C"])
    dt = jnp.einsum("btd,dh->bth", u, params["in_dt"]).astype(jnp.float32)
    dt = jax.nn.softplus(dt + params["dt_bias"])  # (B, T, H) fp32
    return z, x, bm, cm, dt


def _gated_out(params, y: jax.Array, z: jax.Array, cfg: ModelConfig, eps: float = 1e-5):
    """SiLU(z)-gated RMSNorm then output projection."""
    yf = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    ms = jnp.mean(jnp.square(yf), axis=-1, keepdims=True)
    yf = yf * jax.lax.rsqrt(ms + eps) * params["gate_norm"].astype(jnp.float32)
    return jnp.einsum("bte,ed->btd", yf.astype(y.dtype), params["out"])


def ssd_chunked(x, bm, cm, dt, a_log, d_skip, chunk: int, init_state=None):
    """SSD dual form. x: (B,T,H,P); bm/cm: (B,T,G,N); dt: (B,T,H) fp32.

    Returns (y (B,T,H,P), final_state (B,H,P,N) fp32).
    """
    b, t, h, p = x.shape
    grp = bm.shape[2]
    n = bm.shape[3]
    q = min(chunk, t)
    if t % q:
        q = t
    nc = t // q
    heads_per_group = h // grp

    a = -jnp.exp(a_log.astype(jnp.float32))  # (H,) negative
    dta = dt * a  # (B,T,H) log-decay per step
    xc = x.reshape(b, nc, q, h, p)
    bc = bm.reshape(b, nc, q, grp, n)
    cc = cm.reshape(b, nc, q, grp, n)
    dtc = dt.reshape(b, nc, q, h)
    dtac = dta.reshape(b, nc, q, h)

    if init_state is None:
        init_state = jnp.zeros((b, h, p, n), jnp.float32)

    def chunk_body(state, inp):
        xq, bq, cq, dtq, dtaq = inp  # (B,Q,H,P), (B,Q,G,N), ..., (B,Q,H)
        cum = jnp.cumsum(dtaq, axis=1)  # (B,Q,H) log-decay prefix
        # intra-chunk: L[i,j] = exp(cum_i - cum_j) * dt_j  for i >= j
        li = cum[:, :, None, :] - cum[:, None, :, :]  # (B,Qi,Qj,H)
        iq = jnp.arange(q)
        causal = iq[:, None] >= iq[None, :]
        decay = jnp.where(causal[None, :, :, None], jnp.exp(li), 0.0)
        lmat = decay * dtq[:, None, :, :]  # (B,Qi,Qj,H)
        scores = jnp.einsum("bigm,bjgm->bijg", cq.astype(jnp.float32), bq.astype(jnp.float32))
        scores = jnp.repeat(scores, heads_per_group, axis=3) * lmat  # (B,Qi,Qj,H)
        y_intra = jnp.einsum("bijh,bjhp->bihp", scores, xq.astype(jnp.float32))
        # inter-chunk: contribution of the carried state
        state_decay = jnp.exp(cum)  # (B,Q,H)
        cqh = jnp.repeat(cq, heads_per_group, axis=2)  # (B,Q,H,N)
        y_inter = jnp.einsum("bqhn,bhpn->bqhp", cqh.astype(jnp.float32), state) * state_decay[..., None]
        y = y_intra + y_inter
        # state update: S' = S * exp(sum dta) + sum_j exp(cum_Q - cum_j) dt_j B_j x_j
        total = cum[:, -1, :]  # (B,H)
        w_j = jnp.exp(total[:, None, :] - cum) * dtq  # (B,Q,H)
        bqh = jnp.repeat(bq, heads_per_group, axis=2)  # (B,Q,H,N)
        ds = jnp.einsum("bqhp,bqhn->bhpn", xq.astype(jnp.float32) * w_j[..., None], bqh.astype(jnp.float32))
        state = state * jnp.exp(total)[:, :, None, None] + ds
        return state, y.astype(xq.dtype)  # stack in model dtype (memory)

    inputs = tuple(jnp.moveaxis(v, 1, 0) for v in (xc, bc, cc, dtc, dtac))
    final_state, ys = jax.lax.scan(chunk_body, init_state, inputs)
    y = jnp.moveaxis(ys, 0, 1).reshape(b, t, h, p)
    skip = (x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]).astype(x.dtype)
    return y + skip, final_state


def apply_ssm(params, u: jax.Array, cfg: ModelConfig, rules: LogicalRules | None = None, init_state=None, return_cache: bool = False):
    """Full-sequence Mamba-2 mixer. u: (B, T, d) -> (B, T, d).

    With ``return_cache`` also returns the decode-continuation state
    (matches :func:`ssm_cache_shapes`)."""
    b, t, _ = u.shape
    h, p = cfg.ssm_nheads, cfg.ssm_head_dim
    z, x0, bm0, cm0, dt = _project_inputs(params, u, cfg)
    x = jax.nn.silu(_causal_conv(x0, params["conv_x"]).astype(jnp.float32)).astype(x0.dtype)
    bm = jax.nn.silu(_causal_conv(bm0, params["conv_B"]).astype(jnp.float32)).astype(bm0.dtype)
    cm = jax.nn.silu(_causal_conv(cm0, params["conv_C"]).astype(jnp.float32)).astype(cm0.dtype)
    x = shard_as(x, ("batch", "seq", "ssm_inner"), rules)
    xh = x.reshape(b, t, h, p)
    y, state = ssd_chunked(xh, bm, cm, dt, params["A_log"], params["D"], cfg.ssm_chunk, init_state)
    out = _gated_out(params, y.reshape(b, t, -1), z, cfg)
    if return_cache:
        km1 = cfg.conv_kernel - 1
        cache = {
            "ssd": state,
            "conv_x": x0[:, -km1:].astype(jnp.bfloat16),
            "conv_B": bm0[:, -km1:].astype(jnp.bfloat16),
            "conv_C": cm0[:, -km1:].astype(jnp.bfloat16),
        }
        return out, cache
    return out


def ssm_decode_step(params, u: jax.Array, cache: dict, cfg: ModelConfig):
    """One-token recurrent step. u: (B, 1, d); cache per ssm_cache_shapes.

    Returns (out (B, 1, d), new_cache).
    """
    b = u.shape[0]
    h, p = cfg.ssm_nheads, cfg.ssm_head_dim
    grp = cfg.ssm_groups
    z, x, bm, cm, dt = _project_inputs(params, u, cfg)

    def conv_step(state, new, w):
        # state: (B, K-1, C...), new: (B, 1, C...), w: (K, C...)
        hist = jnp.concatenate([state, new], axis=1)  # (B, K, C...)
        k = w.shape[0]
        h2 = hist.reshape(b, k, -1)
        out = jnp.einsum("bkc,kc->bc", h2, w.reshape(k, -1))
        return out.reshape(new.shape[0], *new.shape[2:]), hist[:, 1:]

    x1, conv_x = conv_step(cache["conv_x"], x, params["conv_x"])
    b1, conv_b = conv_step(cache["conv_B"], bm, params["conv_B"])
    c1, conv_c = conv_step(cache["conv_C"], cm, params["conv_C"])
    x1 = jax.nn.silu(x1.astype(jnp.float32))  # (B, di)
    b1 = jax.nn.silu(b1.astype(jnp.float32))  # (B, G, N)
    c1 = jax.nn.silu(c1.astype(jnp.float32))

    a = -jnp.exp(params["A_log"].astype(jnp.float32))  # (H,)
    dt1 = dt[:, 0]  # (B, H)
    da = jnp.exp(dt1 * a)  # (B, H)
    xh = x1.reshape(b, h, p)
    heads_per_group = h // grp
    bh = jnp.repeat(b1, heads_per_group, axis=1)  # (B, H, N)
    ch = jnp.repeat(c1, heads_per_group, axis=1)
    state = cache["ssd"] * da[..., None, None] + jnp.einsum(
        "bhp,bhn->bhpn", xh * dt1[..., None], bh
    )
    y = jnp.einsum("bhpn,bhn->bhp", state, ch) + xh * params["D"].astype(jnp.float32)[None, :, None]
    out = _gated_out(params, y.reshape(b, 1, -1).astype(u.dtype), z, cfg)
    new_cache = {"ssd": state, "conv_x": conv_x, "conv_B": conv_b, "conv_C": conv_c}
    return out, new_cache
