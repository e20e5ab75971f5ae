"""Pure-jnp oracles for every Pallas kernel (the correctness references).

Each oracle is the mathematically transparent O(T^2)/dense formulation —
slow and memory-hungry by design. Kernel tests sweep shapes/dtypes and
assert_allclose against these. ``chunked_mha_ref`` is the same attention
query-chunked, the form ``kernels/ops.py`` runs off the TPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

DEFAULT_Q_CHUNK = 512


def chunked_mha_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    q_chunk: int = DEFAULT_Q_CHUNK,
    q_offset: jax.Array | int = 0,
    window: jax.Array | None = None,
) -> jax.Array:
    """Query-chunked attention. q: (B,T,H,hd); k,v: (B,S,KV,hd) -> (B,T,H,hd).

    ``lax.scan`` over query blocks, so the lowered program never
    materializes a (T, S) score tensor — the jnp analogue of the
    flash-attention memory profile, which keeps dry-run lowering at full
    size honest. ``q_offset``: absolute position of q[0] (a chunked-prefill
    block may start past 0). ``window`` (1,): a causal row at position p
    sees the keys above ``p - window`` only."""
    b, t, h, hd = q.shape
    num_kv = k.shape[2]
    qg = q.reshape(b, t, num_kv, h // num_kv, hd)
    s_len = k.shape[1]
    chunk = min(q_chunk, t)
    if t % chunk != 0:  # fall back to one block for odd lengths (tests)
        chunk = t
    n_chunks = t // chunk
    qg = qg.reshape(b, n_chunks, chunk, num_kv, h // num_kv, hd)
    k_idx = jnp.arange(s_len)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))

    def body(carry, inp):
        q_blk, blk_i = inp  # (B, chunk, KV, G, hd)
        scores = jnp.einsum("btkgh,bskh->bkgts", q_blk, k, preferred_element_type=jnp.float32) * scale
        if causal:
            q_idx = blk_i * chunk + jnp.arange(chunk) + q_offset
            mask = k_idx[None, :] <= q_idx[:, None]
            if window is not None:
                mask = mask & (k_idx[None, :] > q_idx[:, None] - window[0])
            scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v)
        return carry, out

    _, outs = jax.lax.scan(body, None, (jnp.moveaxis(qg, 1, 0), jnp.arange(n_chunks)))
    # outs: (n_chunks, B, chunk, KV, G, hd)
    return jnp.moveaxis(outs, 0, 1).reshape(b, t, h, hd)


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = True, q_offset: int = 0) -> jax.Array:
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) with H % KV == 0 -> (B, T, H, hd)."""
    b, t, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, t, kv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    scores = jnp.einsum("btkgh,bskh->bkgts", qg, k, preferred_element_type=jnp.float32) * scale
    if causal:
        qi = jnp.arange(t) + q_offset
        si = jnp.arange(s)
        mask = si[None, :] <= qi[:, None]
        scores = jnp.where(mask[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgts,bskh->btkgh", probs.astype(v.dtype), v)
    return out.reshape(b, t, h, hd)


def decode_attn_ref(q: jax.Array, k: jax.Array, v: jax.Array, cur_len: jax.Array,
                    window: jax.Array | None = None) -> jax.Array:
    """q: (B, H, hd); k, v: (B, S, KV, hd); cur_len: (B,) -> (B, H, hd).
    ``window`` (1,): slot b sees keys ``[cur_len[b] - window, cur_len[b])``."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    qg = q.reshape(b, kv, g, hd)
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, jnp.float32))
    scores = jnp.einsum("bkgh,bskh->bkgs", qg, k, preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(s)[None, :] < cur_len[:, None]  # (B, S)
    if window is not None:
        mask = mask & (jnp.arange(s)[None, :] >= cur_len[:, None] - window[0])
    scores = jnp.where(mask[:, None, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs.astype(v.dtype), v)
    return out.reshape(b, h, hd)


def ssd_ref(x, bm, cm, dt, a_log, d_skip):
    """Naive O(T^2) SSD (exact dual form, no chunking).

    x: (B,T,H,P); bm/cm: (B,T,G,N); dt: (B,T,H) fp32; a_log, d_skip: (H,)
    -> (B,T,H,P) fp32 and final state (B,H,P,N) fp32."""
    b, t, h, p = x.shape
    grp = bm.shape[2]
    hpg = h // grp
    a = -jnp.exp(a_log.astype(jnp.float32))
    dta = dt.astype(jnp.float32) * a  # (B,T,H)
    cum = jnp.cumsum(dta, axis=1)
    # decay[i, j] = exp(cum_i - cum_j), i >= j
    li = cum[:, :, None, :] - cum[:, None, :, :]  # (B, Ti, Tj, H)
    iq = jnp.arange(t)
    causal = iq[:, None] >= iq[None, :]
    decay = jnp.where(causal[None, :, :, None], jnp.exp(li), 0.0)
    lmat = decay * dt.astype(jnp.float32)[:, None, :, :]  # (B,Ti,Tj,H)
    scores = jnp.einsum("bign,bjgn->bijg", cm.astype(jnp.float32), bm.astype(jnp.float32))
    scores = jnp.repeat(scores, hpg, axis=3) * lmat
    y = jnp.einsum("bijh,bjhp->bihp", scores, x.astype(jnp.float32))
    y = y + x.astype(jnp.float32) * d_skip.astype(jnp.float32)[None, None, :, None]
    # final state
    w_j = jnp.exp(cum[:, -1:, :] - cum) * dt.astype(jnp.float32)  # (B,T,H)
    bh = jnp.repeat(bm, hpg, axis=2).astype(jnp.float32)  # (B,T,H,N)
    state = jnp.einsum("bthp,bthn->bhpn", x.astype(jnp.float32) * w_j[..., None], bh)
    return y, state


def gmm_ref(x: jax.Array, w: jax.Array, group_sizes: jax.Array,
            layer: jax.Array | None = None) -> jax.Array:
    """Grouped GEMM of rows sorted by group. x: (M, d); w: (E, d, f), or
    (L, E, d, f) with ``layer`` (1,) naming the layer; group_sizes: (E,)
    -> (M, f); rows past the groups' sum come out zero."""
    if layer is not None:
        w = w[layer[0]]
    out = jax.lax.ragged_dot(x, w, group_sizes, preferred_element_type=jnp.float32)
    return out.astype(x.dtype)
