"""GQA/MQA attention: train/prefill (causal), decode (KV cache), paged
decode and chunked prefill over the KV arena, and cross-attention for
enc-dec.

Projections and cache updates live here; the attention itself is one call
into ``kernels/ops.py``, which picks the Pallas kernel on a TPU and the jnp
reference elsewhere. The reference's full-sequence path is query-chunked, so
the lowered program never materializes a (T, S) score tensor and keeps the
flash-attention memory profile for roofline purposes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, layer_rope, rms_norm_1d
from repro.models.params import ParamDef

#: the window a full layer of a windowed configuration passes: wider than
#: any sequence, so its lower key bound is 0
NO_WINDOW = 1 << 30


def layer_window(cfg: ModelConfig, layer) -> jax.Array | None:
    """How many keys back from each query layer ``layer`` (an int or a
    traced int32) attends to, as an int32 (1,) for the kernels' scalar
    prefetch; None for a configuration without a window, whose attention
    takes the unwindowed path."""
    if not cfg.sliding_window:
        return None
    w = jnp.where(cfg.full_layer(layer), NO_WINDOW, cfg.sliding_window)
    return jnp.asarray(w, jnp.int32).reshape(1)


def attn_defs(cfg: ModelConfig):
    d, h, kv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    defs = {
        # fan-in is d for the projections in and h*hd for the one out: a
        # (d, h, hd) leaf's default axis (-2) would be h, giving q/k entries
        # of std ~sqrt(d/h) and a saturated, chaotic softmax at init
        "wq": ParamDef((d, h, hd), ("embed_fsdp", "heads", "head_dim"), scale_axis=-3),
        "wk": ParamDef((d, kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), scale_axis=-3),
        "wv": ParamDef((d, kv, hd), ("embed_fsdp", "kv_heads", "head_dim"), scale_axis=-3),
        "wo": ParamDef((h, hd, d), ("heads", "head_dim", "embed_fsdp"), scale_axis=(-3, -2)),
    }
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
    return defs


def qkv_project(params, x: jax.Array, cfg: ModelConfig, positions: jax.Array | None,
                layer=None):
    """x: (B, T, d) -> q (B,T,H,hd), k/v (B,T,KV,hd); applies QK-norm + RoPE
    (layer ``layer``'s kind of RoPE, see ``layers.layer_rope``)."""
    q = jnp.einsum("btd,dhk->bthk", x, params["wq"])
    k = jnp.einsum("btd,dhk->bthk", x, params["wk"])
    v = jnp.einsum("btd,dhk->bthk", x, params["wv"])
    if "q_norm" in params:
        q = rms_norm_1d(params["q_norm"], q)
        k = rms_norm_1d(params["k_norm"], k)
    if positions is not None:
        freqs, scale = layer_rope(cfg, q.shape[-1], layer)
        q = apply_rope(q, positions, freqs, scale)
        k = apply_rope(k, positions, freqs, scale)
    return q, k, v


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool,
                   window: jax.Array | None = None) -> jax.Array:
    """q: (B,T,H,hd); k,v: (B,S,KV,hd) -> (B,T,H,hd). The flash kernel on a
    TPU, the query-chunked jnp reference elsewhere (``kernels/ops.py``).
    ``window`` (see :func:`layer_window`) limits each row to its last keys."""
    from repro.kernels import ops as kops

    return kops.attention(q, k, v, causal=causal, window=window)


def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cur_len: jax.Array,
    window: jax.Array | None = None,
) -> jax.Array:
    """One-token attention over a (possibly sequence-sharded) KV cache.

    q: (B,1,H,hd); caches: (B,S,KV,hd); cur_len: (B,) valid lengths
    (positions < cur_len attend). GSPMD turns the softmax reduction over a
    'model'-sharded S into the flash-decoding partial-softmax all-reduce.
    """
    from repro.kernels import ops as kops

    if k_cache.dtype != q.dtype:
        # quantized (e.g. fp8) KV cache: HBM reads happen at the narrow
        # dtype; the upconvert fuses into the attention kernel on TPU
        k_cache = k_cache.astype(q.dtype)
        v_cache = v_cache.astype(q.dtype)
    return kops.decode_attention(q[:, 0], k_cache, v_cache, cur_len, window)[:, None]


def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cur_len: jax.Array,
    window: jax.Array | None = None,
) -> jax.Array:
    """One-token attention over a paged KV arena.

    q: (B,1,H,hd); pages: (P, KV, page, hd); block_table: (B, n) int32 rows
    of physical page ids (padded entries point at the arena's scratch page);
    cur_len: (B,) valid lengths; ``window`` (1,) keeps slot b to its keys
    ``[max(0, cur_len[b] - window), cur_len[b])``. On TPU the
    block-table-indirect split-K kernel reads pages directly; elsewhere the
    pages are gathered contiguous and the dense decode reference runs on
    them, bit-exact against a dense cache of the same width
    (``kernels/ops.py``)."""
    from repro.kernels import ops as kops

    return kops.paged_decode_attention(q[:, 0], k_pages, v_pages, block_table, cur_len,
                                       window)[:, None]


def paged_chunk_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    start: jax.Array,
    window: jax.Array | None = None,
) -> jax.Array:
    """Causal attention for one chunked-prefill block over a paged arena.

    q: (1, C, H, hd) — C chunk rows whose absolute positions begin at
    ``start`` (shape (1,) int32); pages: (P, KV, page, hd); block_table:
    (1, n). Each chunk row attends to every position <= its own absolute
    position (and, with ``window`` (1,), above it minus the window), exactly
    like the matching rows of a dense causal prefill. On TPU the
    block-table-indirect chunk kernel reads pages directly; elsewhere one
    gather rebuilds the contiguous view and the q-chunked reference runs
    with ``q_offset=start``, bit-exact against dense."""
    from repro.kernels import ops as kops

    return kops.paged_chunk_attention(q, k_pages, v_pages, block_table, start, window)


def attn_output(params, attn: jax.Array) -> jax.Array:
    return jnp.einsum("bthk,hkd->btd", attn, params["wo"])


def update_kv_cache(
    k_cache: jax.Array,
    v_cache: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    positions: jax.Array,
):
    """Scatter new K/V rows (B, T_new, KV, hd) into caches at ``positions``
    (B, T_new) — per-example positions support continuous batching."""
    b = k_cache.shape[0]
    batch_idx = jnp.broadcast_to(jnp.arange(b)[:, None], positions.shape)
    k_cache = k_cache.at[batch_idx, positions].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[batch_idx, positions].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache


def update_paged_kv(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    block_table: jax.Array,
    cur_len: jax.Array,
):
    """Scatter one new K/V token (B, 1, KV, hd) into the page arena at each
    sequence's write position: physical page ``bt[b, cur//page]``, row
    ``cur % page``. Masked slots carry an all-scratch block-table row with
    ``cur_len == 0``, so their write lands in the reserved scratch page."""
    page = k_pages.shape[2]
    b = block_table.shape[0]
    logical = cur_len // page
    phys = block_table[jnp.arange(b), logical]  # (B,) physical page ids
    slot = cur_len % page
    k_pages = k_pages.at[phys, :, slot].set(k_new[:, 0].astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, slot].set(v_new[:, 0].astype(v_pages.dtype))
    return k_pages, v_pages


def update_paged_kv_chunk(
    k_pages: jax.Array,
    v_pages: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    block_table: jax.Array,
    start: jax.Array,
    valid: jax.Array,
):
    """Scatter one prefill chunk's K/V rows (1, C, KV, hd) into the page
    arena: chunk row i lands at logical position ``start + i`` -> physical
    page ``bt[0, (start+i)//page]``, slot ``(start+i) % page``. Rows at
    ``i >= valid`` are padding (the chunk is padded to a power of two for
    compile-cache reuse): their writes are routed to the reserved scratch
    page, same contract as a masked decode slot."""
    page = k_pages.shape[2]
    c = k_new.shape[1]
    idx = jnp.arange(c)
    pos = start[0] + idx
    logical = jnp.clip(pos // page, 0, block_table.shape[1] - 1)
    phys = jnp.where(idx < valid[0], block_table[0, logical], 0)  # (C,)
    slot = pos % page
    k_pages = k_pages.at[phys, :, slot].set(k_new[0].astype(k_pages.dtype))
    v_pages = v_pages.at[phys, :, slot].set(v_new[0].astype(v_pages.dtype))
    return k_pages, v_pages
