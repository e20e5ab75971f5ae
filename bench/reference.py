"""Plain float32 reference of the dense family, independent of the program.

A configuration file names this module under ``"reference"`` when its
model is the dense family this file describes, and it refuses any other
(``flops.require_dense``). The equations are the program's written out in
``jax.numpy`` at float32 with the highest matmul precision: pre-LayerNorm
blocks, rotary embedding over the whole head dimension (halves rotated),
causal softmax attention with grouped KV heads, a tanh-GELU or a SiLU-gated
MLP, a final LayerNorm and an untied head. Departures from the published
models are listed in each configuration's file.

What every reference shares (the weight recipe, the fp8 control, blocked
attention, the stage-by-stage forward and the gaps) is in
``reference_common.py``; ``bench/tests/test_reference.py`` checks on the
CPU that the draws equal the deployed leaves bit for bit.

``control=True`` is the fp8 control: every matmul operand (weights per
output column, activations per row, and q, k, probabilities and v in
attention) is rounded to float8_e4m3fn with its own scale before a float32
matmul. It is the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

import reference_common as rc
from flops import require_dense

LN_EPS = 1e-5


def _shared_leaves(m: dict) -> list[rc.Leaf]:
    """The embedding, head and final norm, in sorted-name order."""
    d, v = m["d_model"], m["vocab_size"]
    leaves = [("embed.head", (d, v), "normal", d),
              ("embed.table", (v, d), "embed", 1),
              ("ln_f.bias", (d,), "zeros", 1),
              ("ln_f.scale", (d,), "ones", 1)]
    if m.get("tie_embeddings"):
        leaves.pop(0)
    return leaves


def _stage_leaves(m: dict, per: int) -> list[rc.Leaf]:
    d, h, kv, hd, f = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"], m["d_ff"]
    attn = [("attn.wk", (per, d, kv, hd), "normal", d),
            ("attn.wo", (per, h, hd, d), "normal", h * hd),
            ("attn.wq", (per, d, h, hd), "normal", d),
            ("attn.wv", (per, d, kv, hd), "normal", d)]
    norms = [(f"{ln}.{p}", (per, d), "zeros" if p == "bias" else "ones", 1)
             for ln in ("ln1", "ln2") for p in ("bias", "scale")]
    if m["act"] == "silu":
        mlp = [("mlp.wi_gate", (per, d, f), "normal", d),
               ("mlp.wi_up", (per, d, f), "normal", d),
               ("mlp.wo", (per, f, d), "normal", f)]
    else:
        mlp = [("mlp.wi", (per, d, f), "normal", d), ("mlp.wo", (per, f, d), "normal", f)]
    return attn + norms + mlp


def _layernorm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _layer(w: dict, x: jax.Array, index, *, act: str, theta: float, fp8: bool) -> jax.Array:
    del index  # every layer of the dense family is alike
    f32, mm = rc.f32, rc.mm
    t, d = x.shape
    hn = _layernorm(x, f32(w["ln1.scale"]), f32(w["ln1.bias"]))
    wq, wk, wv, wo = (f32(w[n]) for n in ("attn.wq", "attn.wk", "attn.wv", "attn.wo"))
    h, hd, kv = wq.shape[1], wq.shape[2], wk.shape[1]
    q = mm(hn, wq.reshape(d, h * hd), fp8).reshape(t, h, hd)
    k = mm(hn, wk.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    v = mm(hn, wv.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    q, k = rc.rope(q, theta), rc.rope(k, theta)
    a = rc.attention(q, k, v, fp8).reshape(t, h * hd)
    x = x + mm(a, wo.reshape(h * hd, d), fp8)
    hn = _layernorm(x, f32(w["ln2.scale"]), f32(w["ln2.bias"]))
    if act == "silu":
        u = jax.nn.silu(mm(hn, f32(w["mlp.wi_gate"]), fp8)) * mm(hn, f32(w["mlp.wi_up"]), fp8)
    else:
        u = jax.nn.gelu(mm(hn, f32(w["mlp.wi"]), fp8), approximate=True)
    return x + mm(u, f32(w["mlp.wo"]), fp8)


def _final_norm(shared: dict, x: jax.Array) -> jax.Array:
    return _layernorm(x, rc.f32(shared["ln_f.scale"]), rc.f32(shared["ln_f.bias"]))


def _params(m: dict) -> dict:
    return {"act": m["act"], "theta": float(m["rope_theta"])}


DENSE = rc.Equations(_shared_leaves, _stage_leaves, _layer, _final_norm, _params)


def shared_weights(m: dict, weight_seed: int) -> dict:
    require_dense(m)
    return rc.shared_weights(DENSE, m, weight_seed)


def stage_weights(m: dict, weight_seed: int, i: int) -> dict:
    require_dense(m)
    return rc.stage_weights(DENSE, m, weight_seed, i)


def served_gaps(m: dict, weight_seed: int, seqs: list[tuple[np.ndarray, np.ndarray]],
                *, control: bool = False, log=None) -> list:
    """``reference_common.served_gaps`` (which see) with the dense equations."""
    require_dense(m)
    return rc.served_gaps(DENSE, m, weight_seed, seqs, control=control, log=log)
