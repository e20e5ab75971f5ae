"""Paged flash-decoding attention — block-table indirection over the
split-K decode schedule.

Same partial-softmax sweep as ``decode_attention.py`` (grid minor axis walks
the sequence's pages, (m, l, acc) carried in VMEM scratch), but K/V live in
a shared page arena instead of a per-sequence contiguous buffer: the page
index maps read the sequence's *block table* — scalar-prefetched so the
physical page id is known before the kernel body runs and the DMA fetches
exactly that page. Sequences of any ragged length batch together; pages
past the length are skipped (no compute, and the index map re-points them
at the last live page so no DMA is issued), and padded block-table entries
point at the arena's reserved scratch page.

Layout: the arena is head-major, one layer's pages are
``(num_pages, KV, page, hd)``, so a block squeezes the page and KV-head
axes and its last two dims are (page, hd) — the tiling the TPU compiler
accepts. One grid cell serves the G query heads that share a KV head, so a
page is fetched once per group and its scores are one MXU matmul.

``gather_pages`` is the non-TPU fallback: it reconstructs the contiguous
(B, S, KV, hd) view with one advanced-indexing gather, which XLA fuses into
the surrounding program (see ``kernels/ops.py`` for the dispatch).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

DEFAULT_BLOCK_Q = 128


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """pages: (P, KV, page, hd); block_table: (B, n) -> (B, n*page, KV, hd).

    The contiguous-gather fallback: one XLA gather rebuilds each sequence's
    logical cache from its pages (garbage past cur_len — callers mask)."""
    b, n = block_table.shape
    _, kv, page, hd = pages.shape
    return jnp.swapaxes(pages[block_table], 2, 3).reshape(b, n * page, kv, hd)


def _paged_kernel(
    bt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, page: int, num_page_blocks: int,
):
    ib, ik = pl.program_id(0), pl.program_id(2)
    cur = len_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # an empty batcher slot (cur_len 0) computes nothing: l stays 0 and
    # _finalize emits exact zeros, not a mean of scratch-page contents
    @pl.when(ik * page < cur)
    def _body():
        s = osm.scores(q_ref[...], k_ref[...], scale)  # (G, page)
        # logical position of this page's rows = page index * page + row
        cols = ik * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < cur, s, osm.NEG_INF)
        m_ref[...], l_ref[...], acc_ref[...] = osm.step(
            s, v_ref[...], m_ref[...], l_ref[...], acc_ref[...]
        )

    @pl.when(ik == num_page_blocks - 1)
    def _finalize():
        o_ref[...] = osm.finalize(l_ref[...], acc_ref[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cur_len: jax.Array,
    *,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (P, KV, page, hd);
    block_table: (B, n_pages) int32; cur_len: (B,) -> (B, H, hd)."""
    b, h, hd = q.shape
    _, kv, page, _ = k_pages.shape
    n = block_table.shape[1]
    g = h // kv
    scale = 1.0 / (hd**0.5)

    def page_map(ib, j, ik, bt, ln):  # pages past cur_len re-read the last live one
        return (bt[ib, jnp.minimum(ik, jnp.maximum(ln[ib] - 1, 0) // page)], j, 0, 0)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page, num_page_blocks=n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, cur_len
        grid=(b, kv, n),
        in_specs=[
            pl.BlockSpec((None, None, g, hd), lambda ib, j, ik, bt, ln: (ib, j, 0, 0)),
            pl.BlockSpec((None, None, page, hd), page_map),
            pl.BlockSpec((None, None, page, hd), page_map),
        ],
        out_specs=pl.BlockSpec((None, None, g, hd), lambda ib, j, ik, bt, ln: (ib, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",  # the kernel's HLO op name, whatever jit wraps it
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), cur_len.astype(jnp.int32),
      q.reshape(b, kv, g, hd), k_pages, v_pages)
    return out.reshape(b, h, hd)


def _paged_chunk_kernel(
    bt_ref, start_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
    *, scale: float, page: int, num_page_blocks: int, block_q: int, groups: int,
):
    """Chunked-prefill attention over the page arena: a block of ``block_q``
    query rows of one prefill chunk (the chunk starts at absolute position
    ``start``), for each of the G heads sharing a KV head, sweeps the
    sequence's pages with the decode kernel's online softmax, carrying
    per-row (m, l, acc) in VMEM scratch. Row i masks columns past its own
    absolute position (causal); pages wholly past the block's last row are
    skipped."""
    ib, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    row0 = start_ref[ib] + iq * block_q  # absolute position of the block's row 0

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * page <= row0 + block_q - 1)
    def _body():
        k = k_ref[...]  # (page, hd)
        v = v_ref[...]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 0)
        cols = ik * page + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 1)
        causal = cols <= rows
        for gi in range(groups):  # static: G (bq, hd) x (hd, page) MXU matmuls
            s = jnp.where(causal, osm.scores(q_ref[gi], k, scale), osm.NEG_INF)
            m_ref[gi], l_ref[gi], acc_ref[gi] = osm.step(
                s, v, m_ref[gi], l_ref[gi], acc_ref[gi]
            )

    @pl.when(ik == num_page_blocks - 1)
    def _finalize():
        for gi in range(groups):
            o_ref[gi] = osm.finalize(l_ref[gi], acc_ref[gi], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret"))
def paged_chunk_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    start: jax.Array,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, C, H, hd); k_pages/v_pages: (P, KV, page, hd);
    block_table: (B, n) int32; start: (B,) absolute position of q[:, 0]
    -> (B, C, H, hd)."""
    b, c, h, hd = q.shape
    _, kv, page, _ = k_pages.shape
    n = block_table.shape[1]
    g = h // kv
    block_q = min(block_q, c)
    if c % block_q:
        raise ValueError(f"chunk C={c} must divide block_q={block_q}")
    scale = 1.0 / (hd**0.5)

    def page_map(ib, j, iq, ik, bt, st):  # pages past the block's last row: no DMA
        return (bt[ib, jnp.minimum(ik, (st[ib] + (iq + 1) * block_q - 1) // page)], j, 0, 0)

    def q_map(ib, j, iq, ik, bt, st):
        return (ib, j, 0, iq, 0)

    kernel = functools.partial(
        _paged_chunk_kernel, scale=scale, page=page, num_page_blocks=n,
        block_q=block_q, groups=g,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,  # block_table, start
        grid=(b, kv, c // block_q, n),
        in_specs=[
            pl.BlockSpec((None, None, g, block_q, hd), q_map),
            pl.BlockSpec((None, None, page, hd), page_map),
            pl.BlockSpec((None, None, page, hd), page_map),
        ],
        out_specs=pl.BlockSpec((None, None, g, block_q, hd), q_map),
        scratch_shapes=[
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, 1), jnp.float32),
            pltpu.VMEM((g, block_q, hd), jnp.float32),
        ],
    )
    # (B, C, H, hd) -> head-major (B, KV, G, C, hd): each block is the G
    # heads of one KV head over block_q rows
    qh = q.reshape(b, c, kv, g, hd).transpose(0, 2, 3, 1, 4)
    out = pl.pallas_call(
        kernel,
        name="paged_chunk_attention",  # the kernel's HLO op name, whatever jit wraps it
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(qh.shape, q.dtype),
        interpret=interpret,
    )(block_table.astype(jnp.int32), start.astype(jnp.int32), qh, k_pages, v_pages)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd)
