"""Plain float32 reference of the served models, independent of the program.

It imports nothing of the program and takes nothing the program made. The
weights are drawn again from the run's weight seed by the same recipe the
deployment uses (one key per chain stage, one key per leaf in the order of
the leaves' sorted names, a unit normal scaled by one over the root of the
fan-in and rounded to bfloat16); ``bench/tests/test_reference.py`` checks
on the CPU that the draws equal the deployed leaves bit for bit. The
forward pass is the program's equations written out in ``jax.numpy`` at
float32 with the highest matmul precision: pre-LayerNorm blocks, rotary
embedding over the whole head dimension (halves rotated), causal softmax
attention with grouped KV heads, a tanh-GELU or a SiLU-gated MLP, a final
LayerNorm and an untied head. Departures from the published models are
listed in each configuration's file.

It runs one chain stage at a time and one layer at a time, so it fits on
the chip beside nothing else once the program's state is freed.

``precision="fp8"`` is the control: every matmul operand (weights per
output column, activations per row, and q, k, probabilities and v in
attention) is rounded to float8_e4m3fn with its own scale before a float32
matmul. It is the step below the bfloat16 the configurations state.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

LN_EPS = 1e-5
Q_BLOCK = 512
FP8_MAX = 448.0


# ----------------------------------------------------------------- weights


def chain_groups(num_layers: int, requested: int) -> int:
    """How many layer stages the deployment cuts the model into: the
    largest divisor of the depth not above the requested count."""
    g = min(requested, num_layers)
    while g > 1 and num_layers % g:
        g -= 1
    return max(1, g)


def _leaf_specs_shared(m: dict) -> list[tuple[str, tuple, str, int]]:
    """(name, shape, init, fan_in) of the embedding, head and final norm,
    in sorted-name order."""
    d, v = m["d_model"], m["vocab_size"]
    leaves = [("embed.head", (d, v), "normal", d),
              ("embed.table", (v, d), "embed", 1),
              ("ln_f.bias", (d,), "zeros", 1),
              ("ln_f.scale", (d,), "ones", 1)]
    if m.get("tie_embeddings"):
        leaves.pop(0)
    return leaves


def _leaf_specs_stage(m: dict, per: int) -> list[tuple[str, tuple, str, int]]:
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


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _draw(shape: tuple, init: str, fan_in: int, key: jax.Array) -> jax.Array:
    std = 1.0 if init == "embed" else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _materialize(specs, key) -> dict:
    keys = jax.random.split(key, max(1, len(specs)))
    out = {}
    for (name, shape, init, fan_in), k in zip(specs, keys):
        if init == "zeros":
            out[name] = jnp.zeros(shape, jnp.bfloat16)
        elif init == "ones":
            out[name] = jnp.ones(shape, jnp.bfloat16)
        else:
            out[name] = _draw(shape, init, fan_in, k)
    return out


def stage_keys(weight_seed: int, groups: int) -> jax.Array:
    return jax.random.split(jax.random.PRNGKey(weight_seed), groups + 1)


def shared_weights(m: dict, weight_seed: int) -> dict:
    g = chain_groups(m["num_layers"], m["num_function_groups"])
    return _materialize(_leaf_specs_shared(m), stage_keys(weight_seed, g)[0])


def stage_weights(m: dict, weight_seed: int, i: int) -> dict:
    g = chain_groups(m["num_layers"], m["num_function_groups"])
    per = m["num_layers"] // g
    return _materialize(_leaf_specs_stage(m, per), stage_keys(weight_seed, g)[1 + i])


# ----------------------------------------------------------------- forward


def _fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """a (..., k) @ w (k, n) in float32; the control rounds a per row and
    w per output column to fp8 first."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _layernorm(x, scale, bias):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _rope(x, theta: float):
    """x: (T, heads, hd); positions 0..T-1; the two halves rotate."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, fp8: bool):
    """Causal softmax attention, q (T, H, hd), k/v (T, KV, hd), computed in
    blocks of query rows so no (T, T) score tensor of every head exists."""
    t, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    k = jnp.repeat(k, g, axis=1)  # head j reads KV head j // g
    v = jnp.repeat(v, g, axis=1)
    if fp8:
        k, v = _fp8(k, -1), _fp8(v, -1)
    nb = t // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, h, hd)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        if fp8:
            qi = _fp8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=jax.lax.Precision.HIGHEST)
        s = s / math.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(cols[None, None, :] <= rows[None, :, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if fp8:
            p = _fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)

    return jax.lax.map(block, (jnp.arange(nb), qb)).reshape(t, h, hd)


def _layer(w: dict, x: jax.Array, *, act: str, theta: float, fp8: bool) -> jax.Array:
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    t, d = x.shape
    hn = _layernorm(x, f32(w["ln1.scale"]), f32(w["ln1.bias"]))
    wq, wk, wv, wo = (f32(w[n]) for n in ("attn.wq", "attn.wk", "attn.wv", "attn.wo"))
    h, hd, kv = wq.shape[1], wq.shape[2], wk.shape[1]
    q = _mm(hn, wq.reshape(d, h * hd), fp8).reshape(t, h, hd)
    k = _mm(hn, wk.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    v = _mm(hn, wv.reshape(d, kv * hd), fp8).reshape(t, kv, hd)
    q, k = _rope(q, theta), _rope(k, theta)
    a = _attention(q, k, v, fp8).reshape(t, h * hd)
    x = x + _mm(a, wo.reshape(h * hd, d), fp8)
    hn = _layernorm(x, f32(w["ln2.scale"]), f32(w["ln2.bias"]))
    if act == "silu":
        u = jax.nn.silu(_mm(hn, f32(w["mlp.wi_gate"]), fp8)) * _mm(hn, f32(w["mlp.wi_up"]), fp8)
    else:
        u = jax.nn.gelu(_mm(hn, f32(w["mlp.wi"]), fp8), approximate=True)
    return x + _mm(u, f32(w["mlp.wo"]), fp8)


@functools.partial(jax.jit, static_argnames=("act", "theta", "fp8"))
def _stage(stage: dict, x: jax.Array, *, act: str, theta: float, fp8: bool) -> jax.Array:
    """Every layer of one chain stage, in order (one scan over its stacked
    leaves)."""
    def body(h, w):
        return _layer(w, h, act=act, theta=theta, fp8=fp8), None

    return jax.lax.scan(body, x, stage)[0]


@functools.partial(jax.jit, static_argnames=("fp8",))
def _logits(shared: dict, x: jax.Array, rows: jax.Array, *, fp8: bool) -> jax.Array:
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
    h = _layernorm(x[rows], f32(shared["ln_f.scale"]), f32(shared["ln_f.bias"]))
    return _mm(h, f32(shared["embed.head"]), fp8)


@jax.jit
def _gaps(ref: jax.Array, chosen: jax.Array) -> jax.Array:
    """How far below the reference's best logit each chosen token lies."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


def _padded(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def served_gaps(m: dict, weight_seed: int, seqs: list[tuple[np.ndarray, np.ndarray]],
                *, control: bool = False, log=None) -> list[np.ndarray]:
    """For each (prompt, served tokens): the gap, at every served position,
    between the float32 reference's best logit and the logit of the token
    that was served; with ``control`` also the gap of the token the fp8
    control would put first. Returns one array per sequence (``control``:
    one (served, control) pair per sequence). ``log`` gets a line per
    stage."""
    log = log or (lambda msg: None)
    with jax.default_matmul_precision("highest"):
        g = chain_groups(m["num_layers"], m["num_function_groups"])
        shared = shared_weights(m, weight_seed)
        toks, hs, hs8 = [], [], []
        for prompt, served in seqs:
            full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            n = _padded(len(full))
            pad = np.zeros((n,), np.int32)
            pad[: len(full)] = full
            toks.append(full)
            x = jnp.take(shared["embed.table"], jnp.asarray(pad), axis=0).astype(jnp.float32)
            hs.append(x)
            hs8.append(x)
        kw = dict(act=m["act"], theta=float(m["rope_theta"]))
        for i in range(g):
            stage = stage_weights(m, weight_seed, i)
            hs = [_stage(stage, x, fp8=False, **kw) for x in hs]
            if control:
                hs8 = [_stage(stage, x, fp8=True, **kw) for x in hs8]
            jax.block_until_ready((hs, hs8))
            del stage
            log(f"reference: stage {i + 1} of {g} done ({[x.shape[0] for x in hs]} rows)")
        out = []
        for (prompt, served), x, x8 in zip(seqs, hs, hs8):
            rows = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
            ref = _logits(shared, x, rows, fp8=False)
            got = np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32)))
            if control:
                low = _logits(shared, x8, rows, fp8=True)
                pick = jnp.argmax(low, axis=-1).astype(jnp.int32)
                out.append((got, np.asarray(_gaps(ref, pick))))
            else:
                out.append(got)
        return out
