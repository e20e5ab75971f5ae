"""What every plain float32 reference shares, whatever its equations.

A configuration file names its reference module (``"reference"``). That
module describes its family in an :class:`Equations`: the leaves of the
embedding, head and final norm, the leaves of one chain stage, one layer's
equations and the final norm. This module does the rest, the same for
every family:

- the weight recipe of the deployment: one key per chain stage, one key
  per leaf in the order of the leaves' sorted names, a unit normal scaled
  by one over the root of the fan-in and rounded to bfloat16;
- the fp8 control's rounding of every matmul operand;
- rotary embedding and causal softmax attention in blocks of query rows,
  optionally within a sliding window;
- the stage-by-stage forward (one scan over each stage's stacked leaves,
  so it fits on the chip once the program's state is freed), the logits
  and the gap of each served token below the reference's best.

It imports nothing of the program and takes nothing the program made.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

Q_BLOCK = 512
FP8_MAX = 448.0

#: (name, shape, init, fan_in); init is "normal", "embed", "zeros" or "ones"
Leaf = tuple[str, tuple, str, int]


@dataclasses.dataclass(frozen=True)
class Equations:
    """One family's pieces of a reference.

    ``shared_leaves(m)`` and ``stage_leaves(m, per)`` list the leaves in
    sorted-name order, the stage's stacked over its ``per`` layers.
    ``layer(w, x, index, *, fp8, **params)`` maps x (T, d) through the
    layer whose leaves are ``w`` and whose place in the model is ``index``
    (a traced int32, for families whose layers differ). ``final_norm(shared,
    x)`` precedes the head. ``params(m)`` gives the hashable keyword
    arguments ``layer`` takes from the model dict."""

    shared_leaves: Callable[[dict], list[Leaf]]
    stage_leaves: Callable[[dict, int], list[Leaf]]
    layer: Callable[..., jax.Array]
    final_norm: Callable[[dict, jax.Array], jax.Array]
    params: Callable[[dict], dict]


# ----------------------------------------------------------------- weights


def chain_groups(num_layers: int, requested: int) -> int:
    """How many layer stages the deployment cuts the model into: the
    largest divisor of the depth not above the requested count."""
    g = min(requested, num_layers)
    while g > 1 and num_layers % g:
        g -= 1
    return max(1, g)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _draw(shape: tuple, init: str, fan_in: int, key: jax.Array) -> jax.Array:
    std = 1.0 if init == "embed" else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(jnp.bfloat16)


def _materialize(specs: list[Leaf], key) -> dict:
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


def shared_weights(eq: Equations, m: dict, weight_seed: int) -> dict:
    g = chain_groups(m["num_layers"], m["num_function_groups"])
    return _materialize(eq.shared_leaves(m), stage_keys(weight_seed, g)[0])


def stage_weights(eq: Equations, m: dict, weight_seed: int, i: int) -> dict:
    g = chain_groups(m["num_layers"], m["num_function_groups"])
    per = m["num_layers"] // g
    return _materialize(eq.stage_leaves(m, per), stage_keys(weight_seed, g)[1 + i])


# ----------------------------------------------------------------- forward


def f32(a: jax.Array) -> jax.Array:
    return a.astype(jnp.float32)


def fp8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3fn with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a: jax.Array, w: jax.Array, low: bool) -> jax.Array:
    """a (..., k) @ w (k, n) in float32; the control (``low``) rounds a
    per row and w per output column to fp8 first."""
    if low:
        a, w = fp8(a, -1), fp8(w, 0)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (T, heads, hd); positions 0..T-1; the two halves rotate."""
    t, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q: jax.Array, k: jax.Array, v: jax.Array, low: bool, window=None) -> jax.Array:
    """Causal softmax attention, q (T, H, hd), k/v (T, KV, hd), computed in
    blocks of query rows so no (T, T) score tensor of every head exists.
    With ``window`` (an int, or a traced scalar), row i sees only the keys
    i - window + 1 .. i."""
    t, h, hd = q.shape
    kv = k.shape[1]
    g = h // kv
    k = jnp.repeat(k, g, axis=1)  # head j reads KV head j // g
    v = jnp.repeat(v, g, axis=1)
    if low:
        k, v = fp8(k, -1), fp8(v, -1)
    nb = t // Q_BLOCK
    qb = q.reshape(nb, Q_BLOCK, h, hd)
    cols = jnp.arange(t)

    def block(args):
        i, qi = args
        if low:
            qi = fp8(qi, -1)
        s = jnp.einsum("qhd,khd->hqk", qi, k, precision=jax.lax.Precision.HIGHEST)
        s = s / math.sqrt(hd)
        rows = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        keep = cols[None, None, :] <= rows[None, :, None]
        if window is not None:
            keep = keep & (cols[None, None, :] > rows[None, :, None] - window)
        s = jnp.where(keep, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        if low:
            p = fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)

    return jax.lax.map(block, (jnp.arange(nb), qb)).reshape(t, h, hd)


@functools.partial(jax.jit, static_argnames=("eq", "params", "low"))
def _stage(stage: dict, x: jax.Array, first: jax.Array, *, eq: Equations, params: tuple,
           low: bool) -> jax.Array:
    """Every layer of one chain stage, in order (one scan over its stacked
    leaves); ``first`` is the model index of the stage's first layer."""
    kw = dict(params)
    per = jax.tree.leaves(stage)[0].shape[0]

    def body(h, args):
        w, index = args
        return eq.layer(w, h, index, fp8=low, **kw), None

    return jax.lax.scan(body, x, (stage, first + jnp.arange(per)))[0]


@functools.partial(jax.jit, static_argnames=("eq", "low"))
def _logits(shared: dict, x: jax.Array, rows: jax.Array, *, eq: Equations, low: bool) -> jax.Array:
    h = eq.final_norm(shared, x[rows])
    return mm(h, f32(shared["embed.head"]), low)


@jax.jit
def _gaps(ref: jax.Array, chosen: jax.Array) -> jax.Array:
    """How far below the reference's best logit each chosen token lies."""
    return jnp.max(ref, axis=-1) - jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]


def _padded(n: int) -> int:
    b = Q_BLOCK
    while b < n:
        b *= 2
    return b


def served_gaps(eq: Equations, m: dict, weight_seed: int,
                seqs: list[tuple[np.ndarray, np.ndarray]], *, control: bool = False,
                log=None) -> list:
    """For each (prompt, served tokens): the gap, at every served position,
    between the float32 reference's best logit and the logit of the token
    that was served; with ``control`` also the gap of the token the fp8
    control would put first. Returns one array per sequence (``control``:
    one (served, control) pair per sequence). ``log`` gets a line per
    stage."""
    log = log or (lambda msg: None)
    with jax.default_matmul_precision("highest"):
        g = chain_groups(m["num_layers"], m["num_function_groups"])
        per = m["num_layers"] // g
        shared = shared_weights(eq, m, weight_seed)
        hs, hs8 = [], []
        for prompt, served in seqs:
            full = np.concatenate([prompt, served[:-1]]).astype(np.int32)
            pad = np.zeros((_padded(len(full)),), np.int32)
            pad[: len(full)] = full
            x = jnp.take(shared["embed.table"], jnp.asarray(pad), axis=0).astype(jnp.float32)
            hs.append(x)
            hs8.append(x)
        params = tuple(sorted(eq.params(m).items()))
        for i in range(g):
            stage = stage_weights(eq, m, weight_seed, i)
            first = jnp.int32(i * per)
            hs = [_stage(stage, x, first, eq=eq, params=params, low=False) for x in hs]
            if control:
                hs8 = [_stage(stage, x, first, eq=eq, params=params, low=True) for x in hs8]
            jax.block_until_ready((hs, hs8))
            del stage
            log(f"reference: stage {i + 1} of {g} done ({[x.shape[0] for x in hs]} rows)")
        out = []
        for (prompt, served), x, x8 in zip(seqs, hs, hs8):
            rows = jnp.arange(len(prompt) - 1, len(prompt) - 1 + len(served))
            ref = _logits(shared, x, rows, eq=eq, low=False)
            got = np.asarray(_gaps(ref, jnp.asarray(served, jnp.int32)))
            if control:
                low = _logits(shared, x8, rows, eq=eq, low=True)
                pick = jnp.argmax(low, axis=-1).astype(jnp.int32)
                out.append((got, np.asarray(_gaps(ref, pick))))
            else:
                out.append(got)
        return out
