"""A second family's reference, for the tests: the configuration file
``tiny-window.json`` names it, and it is one file that gives
``reference_common.py`` its pieces. Pre-RMSNorm blocks with no biases, a
SiLU-gated MLP, a final RMSNorm, and a sliding window of
``sliding_window`` keys on every layer but each
``full_attention_every``-th, which attends to every key."""
from __future__ import annotations

import jax
import jax.numpy as jnp

import reference_common as rc

EPS = 1e-6


def _shared_leaves(m):
    d, v = m["d_model"], m["vocab_size"]
    return [("embed.head", (d, v), "normal", d), ("embed.table", (v, d), "embed", 1),
            ("norm_f.scale", (d,), "ones", 1)]


def _stage_leaves(m, per):
    d, h, kv, hd, f = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"], m["d_ff"]
    return [("attn.wk", (per, d, kv, hd), "normal", d), ("attn.wo", (per, h, hd, d), "normal", h * hd),
            ("attn.wq", (per, d, h, hd), "normal", d), ("attn.wv", (per, d, kv, hd), "normal", d),
            ("ln1.scale", (per, d), "ones", 1), ("ln2.scale", (per, d), "ones", 1),
            ("mlp.wi_gate", (per, d, f), "normal", d), ("mlp.wi_up", (per, d, f), "normal", d),
            ("mlp.wo", (per, f, d), "normal", f)]


def _rmsnorm(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + EPS) * scale


def _layer(w, x, index, *, fp8, theta, window, full_every):
    f32, mm = rc.f32, rc.mm
    t, d = x.shape
    wq, wk, wv, wo = (f32(w[n]) for n in ("attn.wq", "attn.wk", "attn.wv", "attn.wo"))
    h, hd, kv = wq.shape[1], wq.shape[2], wk.shape[1]
    hn = _rmsnorm(x, f32(w["ln1.scale"]))
    q = rc.rope(mm(hn, wq.reshape(d, h * hd), fp8).reshape(t, h, hd), theta)
    k = rc.rope(mm(hn, wk.reshape(d, kv * hd), fp8).reshape(t, kv, hd), theta)
    v = mm(hn, wv.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    span = jnp.where((index + 1) % full_every == 0, t, window)
    x = x + mm(rc.attention(q, k, v, fp8, window=span).reshape(t, h * hd), wo.reshape(h * hd, d), fp8)
    hn = _rmsnorm(x, f32(w["ln2.scale"]))
    u = jax.nn.silu(mm(hn, f32(w["mlp.wi_gate"]), fp8)) * mm(hn, f32(w["mlp.wi_up"]), fp8)
    return x + mm(u, f32(w["mlp.wo"]), fp8)


def _final_norm(shared, x):
    return _rmsnorm(x, rc.f32(shared["norm_f.scale"]))


def _params(m):
    return {"theta": float(m["rope_theta"]), "window": m["sliding_window"],
            "full_every": m["full_attention_every"]}


WINDOWED = rc.Equations(_shared_leaves, _stage_leaves, _layer, _final_norm, _params)


def served_gaps(m, weight_seed, seqs, *, control=False, log=None):
    return rc.served_gaps(WINDOWED, m, weight_seed, seqs, control=control, log=log)
