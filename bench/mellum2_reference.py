"""Plain float32 reference of Mellum2-12B-A2.5B, independent of the program.

The configuration file ``bench/configs/mellum2-12b-a2.5b.json`` names this
module under ``"reference"``. The equations follow the published model
(huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct, config.json), in
``jax.numpy`` at float32 with the highest matmul precision:

- pre-RMSNorm blocks (eps ``norm_eps``, 1e-6), no biases, a final RMSNorm
  and an untied head;
- grouped-query attention with rotary embedding over the whole head (the
  two halves rotate). Layer i is a full layer when
  ``i % full_attention_every == full_attention_offset`` (3 of 0..3): it
  attends to every earlier key, with YaRN's frequencies and its cos/sin
  scale (HF ``_compute_yarn_parameters``: factor 16 over 8,192 original
  positions, beta_fast 32, beta_slow 1, attention factor 1.2772588722239782).
  Every other layer attends to its last ``sliding_window`` keys (1,024),
  with plain RoPE at theta 500,000;
- every layer sparse: a float32 router, the softmax over its 64 experts,
  the top 8 renormalised to sum to one, no shared expert; each expert a
  SiLU-gated MLP of width 896. Every expert is computed for every row and
  weighted by its gate, which is zero for an expert the row did not route
  to: no capacity, nothing dropped.

Weights are redrawn from the seed by the deployment's recipe
(``reference_common.py``: one key per chain stage, one key per leaf in
sorted-name order, a unit normal over the root of the fan-in). That recipe
rounds every leaf to bfloat16; the program draws the router in float32
(``models/moe.py``), so this module redraws the router leaf from the same
key without the rounding. The published MTP head is not served and has no
counterpart here.

``control=True`` is the fp8 control of ``reference_common.py``: every
matmul operand, router included, rounded to float8_e4m3fn first.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

import reference_common as rc

ROUTER = "moe.router"


def _shared_leaves(m: dict) -> list[rc.Leaf]:
    d, v = m["d_model"], m["vocab_size"]
    return [("embed.head", (d, v), "normal", d), ("embed.table", (v, d), "embed", 1),
            ("ln_f.scale", (d,), "ones", 1)]


def _stage_leaves(m: dict, per: int) -> list[rc.Leaf]:
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"]
    e, f = m["num_experts"], m["moe_d_ff"]
    return [("attn.wk", (per, d, kv, hd), "normal", d),
            ("attn.wo", (per, h, hd, d), "normal", h * hd),
            ("attn.wq", (per, d, h, hd), "normal", d),
            ("attn.wv", (per, d, kv, hd), "normal", d),
            ("ln1.scale", (per, d), "ones", 1),
            ("ln2.scale", (per, d), "ones", 1),
            (ROUTER, (per, d, e), "normal", d),
            ("moe.wi_gate", (per, e, d, f), "normal", d),
            ("moe.wi_up", (per, e, d, f), "normal", d),
            ("moe.wo", (per, e, f, d), "normal", f)]


def yarn_inv_freq(hd: int, theta: float, factor: float, original: int, beta_fast: float,
                  beta_slow: float) -> np.ndarray:
    """YaRN's inverse frequencies, as HF ``_compute_yarn_parameters``."""
    def dim_of(rotations):
        return hd * math.log(original / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), hd - 1)
    if low == high:
        high += 0.001
    extrapolation = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    interpolation = extrapolation / factor
    ramp = np.clip((np.arange(hd // 2, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    keep = 1.0 - ramp  # the share of the extrapolated frequency
    return (interpolation * (1.0 - keep) + extrapolation * keep).astype(np.float32)


def _rope(x: jax.Array, inv_freq: jax.Array, scale) -> jax.Array:
    """x: (T, heads, hd); positions 0..T-1; cos and sin times ``scale``."""
    t, _, hd = x.shape
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos, sin = (jnp.cos(ang) * scale)[:, None, :], (jnp.sin(ang) * scale)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _experts(w: dict, hn: jax.Array, gates: jax.Array, fp8: bool) -> jax.Array:
    """sum_e gates[:, e] * expert_e(hn), one expert at a time."""
    def one(acc, args):
        wg, wu, wo, g = args
        u = jax.nn.silu(rc.mm(hn, rc.f32(wg), fp8)) * rc.mm(hn, rc.f32(wu), fp8)
        return acc + g[:, None] * rc.mm(u, rc.f32(wo), fp8), None

    args = (w["moe.wi_gate"], w["moe.wi_up"], w["moe.wo"], gates.T)
    return jax.lax.scan(one, jnp.zeros_like(hn), args)[0]


def _layer(w: dict, x: jax.Array, index, *, fp8: bool, theta: float, eps: float, window: int,
           every: int, offset: int, yarn: tuple, attention_factor: float, top_k: int):
    f32, mm = rc.f32, rc.mm
    t, d = x.shape
    wq, wk, wv, wo = (f32(w[n]) for n in ("attn.wq", "attn.wk", "attn.wv", "attn.wo"))
    h, hd, kv = wq.shape[1], wq.shape[2], wk.shape[1]
    full = index % every == offset
    plain = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    inv_freq = jnp.where(full, jnp.asarray(yarn_inv_freq(hd, theta, *yarn)), plain)
    scale = jnp.where(full, attention_factor, 1.0)

    hn = _rmsnorm(x, f32(w["ln1.scale"]), eps)
    q = _rope(mm(hn, wq.reshape(d, h * hd), fp8).reshape(t, h, hd), inv_freq, scale)
    k = _rope(mm(hn, wk.reshape(d, kv * hd), fp8).reshape(t, kv, hd), inv_freq, scale)
    v = mm(hn, wv.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    span = jnp.where(full, t, window)
    x = x + mm(rc.attention(q, k, v, fp8, window=span).reshape(t, h * hd), wo.reshape(h * hd, d), fp8)

    hn = _rmsnorm(x, f32(w["ln2.scale"]), eps)
    probs = jax.nn.softmax(mm(hn, w[ROUTER].astype(jnp.float32), fp8), axis=-1)
    top, idx = jax.lax.top_k(probs, top_k)
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], idx].set(top)
    return x + _experts(w, hn, gates, fp8)


def _final_norm(shared: dict, x: jax.Array, *, eps: float) -> jax.Array:
    return _rmsnorm(x, rc.f32(shared["ln_f.scale"]), eps)


def _params(m: dict) -> dict:
    return {"theta": float(m["rope_theta"]), "eps": float(m["norm_eps"]),
            "window": int(m["sliding_window"]), "every": int(m["full_attention_every"]),
            "offset": int(m["full_attention_offset"]), "top_k": int(m["num_experts_per_tok"]),
            "yarn": (float(m["yarn_factor"]), int(m["yarn_original_max_position"]),
                     float(m["yarn_beta_fast"]), float(m["yarn_beta_slow"])),
            "attention_factor": float(m["yarn_attention_factor"])}


@functools.lru_cache(maxsize=None)
def _equations(eps: float) -> rc.Equations:
    """Mellum2's equations with the final norm's eps (one object per eps,
    as the reference's compiled functions key on it)."""
    return rc.Equations(_shared_leaves, _stage_leaves, _layer,
                        functools.partial(_final_norm, eps=eps), _params)


def equations(m: dict) -> rc.Equations:
    return _equations(float(m["norm_eps"]))


def _with_router(m: dict, stage: dict, weight_seed: int, i: int) -> dict:
    """The stage's leaves with the router drawn as the program draws it:
    the same key, float32, not rounded."""
    specs = _stage_leaves(m, jax.tree.leaves(stage)[0].shape[0])
    g = rc.chain_groups(m["num_layers"], m["num_function_groups"])
    keys = jax.random.split(rc.stage_keys(weight_seed, g)[1 + i], len(specs))
    j = [s[0] for s in specs].index(ROUTER)
    _, shape, _, fan_in = specs[j]
    stage = dict(stage)
    stage[ROUTER] = jax.random.normal(keys[j], shape, jnp.float32) * (1.0 / math.sqrt(fan_in))
    return stage


def shared_weights(m: dict, weight_seed: int) -> dict:
    return rc.shared_weights(equations(m), m, weight_seed)


def stage_weights(m: dict, weight_seed: int, i: int) -> dict:
    return _with_router(m, rc.stage_weights(equations(m), m, weight_seed, i), weight_seed, i)


def forward_logits(m: dict, weight_seed: int, tokens: np.ndarray, *,
                   control: bool = False) -> np.ndarray:
    """The reference's logits (T, V) at every position of ``tokens`` (the
    fp8 control's with ``control``)."""
    with jax.default_matmul_precision("highest"):
        g = rc.chain_groups(m["num_layers"], m["num_function_groups"])
        per = m["num_layers"] // g
        shared = shared_weights(m, weight_seed)
        pad = np.zeros((rc._padded(len(tokens)),), np.int32)
        pad[: len(tokens)] = tokens
        x = jnp.take(shared["embed.table"], jnp.asarray(pad), axis=0).astype(jnp.float32)
        params = tuple(sorted(_params(m).items()))
        for i in range(g):
            x = rc._stage(stage_weights(m, weight_seed, i), x, jnp.int32(i * per),
                          eq=equations(m), params=params, low=control)
        rows = jnp.arange(len(tokens))
        return np.asarray(rc._logits(shared, x, rows, eq=equations(m), low=control))


def served_gaps(m: dict, weight_seed: int, seqs: list[tuple[np.ndarray, np.ndarray]],
                *, control: bool = False, log=None) -> list:
    """``reference_common.served_gaps`` (which see) with Mellum2's equations
    and the router drawn as the program draws it. ``log`` also gets the
    largest gap, which the configuration's limits leave out: a near tie of
    the router that bfloat16 activations split the other way moves it by
    as much as the fp8 control does (PERF.md)."""
    orig = rc.stage_weights
    rc.stage_weights = lambda eq_, m_, ws, i: _with_router(m_, orig(eq_, m_, ws, i), ws, i)
    try:
        out = rc.served_gaps(equations(m), m, weight_seed, seqs, control=control, log=log)
    finally:
        rc.stage_weights = orig
    if log is not None:
        served = [g[0] for g in out] if control else out
        log(f"reference: largest gap {max(float(np.max(g)) for g in served)} served"
            + (f", {max(float(np.max(g[1])) for g in out)} control" if control else ""))
    return out
