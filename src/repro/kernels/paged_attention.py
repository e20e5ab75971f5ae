"""Paged attention over the shared KV page arena: flash-decoding of one
new token per sequence, and chunked prefill.

K/V live in a shared page arena instead of a per-sequence contiguous
buffer; each sequence's *block table* names its pages. The arena is
head-major, one layer's pages are ``(num_pages, KV, page, hd)``, so one
page of every KV head is one contiguous ``(KV, page, hd)`` block.

``paged_decode_attention`` runs one grid step per batch slot, with all KV
heads in it. K and V stay in HBM (``pl.ANY``); the block table and lengths
are scalar-prefetched. Inside the step a ``fori_loop`` walks the slot's
live pages in blocks of ``ppb`` pages — its trip count is
``ceil(cur_len / (ppb * page))``, so pages past the length cost neither a
DMA nor an iteration, and an empty slot (cur_len 0) starts none and writes
exact zeros. Each block's pages are copied, one ``(KV, page, hd)`` DMA per
page for K and one for V, into a double-buffered VMEM scratch
``(2, ppb, KV, page, hd)``: block j+1 is in flight while block j computes.
Per KV head the scores are one ``(G, hd) x (ppb*page, hd)^T`` MXU matmul
folded into the (m, l, acc) online-softmax state; columns at or past the
length are masked in the last block only. ``ppb`` follows from the shapes:
the largest divisor of the table width with at most ``MAX_BLOCK_KEYS`` keys
per block whose double-buffered K+V scratch fits ``SCRATCH_BUDGET_BYTES``.

``paged_chunk_attention`` (chunked prefill) keeps a grid over pages: its
page index maps read the block table, and pages wholly past the chunk's
last row are skipped.

Both kernels take an optional window, scalar-prefetched so that one scan
over layers of two kinds can pass each its own: a query at position p then
sees the keys above ``p - window``. The decode kernel's page loop starts at
the block of the window's first page and copies no page before it; the
chunk kernel's page map sends the pages wholly before a query block's
window to the first page it reads, so they cost no DMA. Without a window
each kernel compiles exactly as it does without the argument.

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
LANES = 128  # the TPU's vector lane width
MAX_BLOCK_KEYS = 1024  # keys per decode DMA block: one score matmul's width
SCRATCH_BUDGET_BYTES = 4 << 20  # the decode kernel's double-buffered K+V blocks


def gather_pages(pages: jax.Array, block_table: jax.Array) -> jax.Array:
    """pages: (P, KV, page, hd); block_table: (B, n) -> (B, n*page, KV, hd).

    The contiguous-gather fallback: one XLA gather rebuilds each sequence's
    logical cache from its pages (garbage past cur_len — callers mask)."""
    b, n = block_table.shape
    _, kv, page, hd = pages.shape
    return jnp.swapaxes(pages[block_table], 2, 3).reshape(b, n * page, kv, hd)


def _pages_per_block(n: int, kv: int, page: int, hd: int, itemsize: int) -> int:
    """The largest divisor of the table width ``n`` whose block holds at most
    ``MAX_BLOCK_KEYS`` keys and whose double-buffered K and V scratch
    (``4 * ppb * KV * page * hd * itemsize``) fits ``SCRATCH_BUDGET_BYTES``;
    at least 1."""
    fits = [
        d for d in range(1, n + 1)
        if n % d == 0 and d * page <= MAX_BLOCK_KEYS
        and 4 * d * kv * page * hd * itemsize <= SCRATCH_BUDGET_BYTES
    ]
    return max(fits, default=1)


def decode_span(cur, window, page: int, ppb: int):
    """(lowest key, first page, first block) a slot of length ``cur``
    attends from under ``window``: keys ``[max(0, cur - window), cur)``;
    the page loop starts at the block holding the lowest key's page."""
    lo = jnp.maximum(cur - window, 0)
    return lo, lo // page, lo // (ppb * page)


def _paged_kernel(
    bt_ref, len_ref, *refs, scale: float, page: int, ppb: int, windowed: bool,
):
    if windowed:
        win_ref, *refs = refs
    q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, m_ref, l_ref, acc_ref = refs
    ib = pl.program_id(0)
    cur = len_ref[ib]
    kv = q_ref.shape[0]
    bk = ppb * page
    live_pages = (cur + page - 1) // page
    num_blocks = (cur + bk - 1) // bk
    lo, first_page, first_blk = decode_span(cur, win_ref[0], page, ppb) if windowed else (0, 0, 0)

    m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def copy_block(blk, slot, start: bool):
        """Start (or wait for) the K and V DMAs of block ``blk``'s live pages
        into buffer ``slot``; a page past the length is never copied."""
        for i in range(ppb):
            idx = blk * ppb + i
            live = idx < live_pages
            if windowed:  # a page wholly before the window is never copied
                live = jnp.logical_and(live, idx >= first_page)

            @pl.when(live)
            def _():
                pid = bt_ref[ib, idx]
                for hbm, buf, sem in ((k_hbm, k_buf, sems.at[0, slot]),
                                      (v_hbm, v_buf, sems.at[1, slot])):
                    copy = pltpu.make_async_copy(hbm.at[pid], buf.at[slot, i], sem)
                    if start:
                        copy.start()
                    else:
                        copy.wait()

    def fold(blk, slot, masked: bool):
        for j in range(kv):  # static: one (G, hd) x (bk, hd)^T matmul per KV head
            k = k_buf[slot, :, j].reshape(bk, -1)
            v = v_buf[slot, :, j].reshape(bk, -1)
            s = osm.scores(q_ref[j], k, scale)  # (G, bk)
            if masked:  # the last block: columns at or past cur_len, and their
                # never-copied rows of V (stale VMEM), contribute exact zeros;
                # with a window, so do the first block's columns below it
                rest = cur - blk * bk
                cols = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                rows = jax.lax.broadcasted_iota(jnp.int32, v.shape, 0)
                keep_s, keep_v = cols < rest, rows < rest
                if windowed:
                    start = lo - blk * bk
                    keep_s = jnp.logical_and(keep_s, cols >= start)
                    keep_v = jnp.logical_and(keep_v, rows >= start)
                s = jnp.where(keep_s, s, osm.NEG_INF)
                v = jnp.where(keep_v, v, jnp.zeros_like(v))
            m_ref[j], l_ref[j], acc_ref[j] = osm.step(s, v, m_ref[j], l_ref[j], acc_ref[j])

    @pl.when(num_blocks > first_blk)
    def _prime():
        copy_block(first_blk, first_blk % 2, start=True)

    def body(blk, carry):
        slot = blk % 2

        @pl.when(blk + 1 < num_blocks)
        def _prefetch():
            copy_block(blk + 1, 1 - slot, start=True)

        copy_block(blk, slot, start=False)
        edge = blk == num_blocks - 1
        if windowed:
            edge = jnp.logical_or(edge, blk == first_blk)
        pl.when(edge)(lambda: fold(blk, slot, True))
        pl.when(jnp.logical_not(edge))(lambda: fold(blk, slot, False))
        return carry

    jax.lax.fori_loop(first_blk, num_blocks, body, 0)
    for j in range(kv):  # an empty slot keeps l == 0: exact zeros
        o_ref[j] = osm.finalize(l_ref[j], acc_ref[j], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret", "pages_per_block"))
def paged_decode_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    block_table: jax.Array,
    cur_len: jax.Array,
    window: jax.Array | None = None,
    *,
    interpret: bool = False,
    pages_per_block: int | None = None,
) -> jax.Array:
    """q: (B, H, hd); k_pages/v_pages: (P, KV, page, hd);
    block_table: (B, n_pages) int32; cur_len: (B,) -> (B, H, hd).

    ``window`` (1,) int32, scalar-prefetched so a scan over layers of two
    kinds can pass it, keeps slot b to the keys
    ``[max(0, cur_len[b] - window), cur_len[b])``: its page loop starts at
    the block of the window's first page, and earlier pages are never
    copied. None compiles the unwindowed kernel.

    ``pages_per_block`` overrides the block size derived from the shapes
    (tests force multi-block sweeps at tiny shapes with it); it must divide
    ``n_pages``."""
    b, h, hd = q.shape
    _, kv, page, _ = k_pages.shape
    n = block_table.shape[1]
    g = h // kv
    scale = 1.0 / (hd**0.5)
    qh = q.reshape(b, kv, g, hd)
    # a page DMA may only slice an HBM array whose minor dim fills whole
    # 128-lane tiles: a narrower head goes in zero-padded (pad lanes score 0
    # and carry 0), at the cost of one pass over the arena per call
    lanes = -(-hd // LANES) * LANES
    if lanes != hd:
        pad = [(0, 0)] * 3 + [(0, lanes - hd)]
        qh, k_pages, v_pages = (jnp.pad(x, pad) for x in (qh, k_pages, v_pages))
    ppb = pages_per_block or _pages_per_block(n, kv, page, lanes, k_pages.dtype.itemsize)
    if n % ppb:
        raise ValueError(f"pages_per_block={ppb} must divide the table width {n}")

    windowed = window is not None
    kernel = functools.partial(_paged_kernel, scale=scale, page=page, ppb=ppb, windowed=windowed)
    prefetch = [block_table.astype(jnp.int32), cur_len.astype(jnp.int32)]
    if windowed:
        prefetch.append(window.astype(jnp.int32).reshape(1))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # block_table, cur_len[, window]
        grid=(b,),
        in_specs=[
            pl.BlockSpec((None, kv, g, lanes), lambda ib, *_: (ib, 0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, kv, g, lanes), lambda ib, *_: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, kv, page, lanes), k_pages.dtype),
            pltpu.VMEM((2, ppb, kv, page, lanes), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),  # (K, V) x buffer slot
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, lanes), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",  # the kernel's HLO op name, whatever jit wraps it
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, lanes), q.dtype),
        interpret=interpret,
    )(*prefetch, qh, k_pages, v_pages)
    return out[..., :hd].reshape(b, h, hd)


def chunk_pages(row0, block_q: int, page: int, window=None):
    """(first, last) page a block of ``block_q`` query rows from absolute
    position ``row0`` reads: the last holds its last row; the first, with
    ``window``, the lowest key its first row sees (row0 - window + 1)."""
    last = (row0 + block_q - 1) // page
    if window is None:
        return 0, last
    return jnp.maximum(row0 - window + 1, 0) // page, last


def _paged_chunk_kernel(
    bt_ref, start_ref, *refs, scale: float, page: int, num_page_blocks: int, block_q: int,
    groups: int, windowed: bool,
):
    """Chunked-prefill attention over the page arena: a block of ``block_q``
    query rows of one prefill chunk (the chunk starts at absolute position
    ``start``), for each of the G heads sharing a KV head, sweeps the
    sequence's pages with the decode kernel's online softmax, carrying
    per-row (m, l, acc) in VMEM scratch. Row i masks columns past its own
    absolute position (causal) and, with a window, columns at or below its
    position minus the window; pages wholly past the block's last row, or
    wholly before its first row's window, are skipped."""
    if windowed:
        win_ref, *refs = refs
    q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    ib, iq, ik = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    row0 = start_ref[ib] + iq * block_q  # absolute position of the block's row 0
    live = ik * page <= row0 + block_q - 1
    if windowed:
        first, _ = chunk_pages(row0, block_q, page, win_ref[0])
        live = jnp.logical_and(live, ik >= first)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _body():
        k = k_ref[...]  # (page, hd)
        v = v_ref[...]
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 0)
        cols = ik * page + jax.lax.broadcasted_iota(jnp.int32, (block_q, page), 1)
        causal = cols <= rows
        if windowed:
            causal = jnp.logical_and(causal, cols > rows - win_ref[0])
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
    window: jax.Array | None = None,
    *,
    block_q: int = DEFAULT_BLOCK_Q,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, C, H, hd); k_pages/v_pages: (P, KV, page, hd);
    block_table: (B, n) int32; start: (B,) absolute position of q[:, 0]
    -> (B, C, H, hd). ``window`` (1,) int32, scalar-prefetched: row p
    attends to keys above ``p - window`` only, and a q block issues no DMA
    for the pages wholly before its first row's window. None compiles the
    unwindowed kernel."""
    b, c, h, hd = q.shape
    _, kv, page, _ = k_pages.shape
    n = block_table.shape[1]
    g = h // kv
    block_q = min(block_q, c)
    if c % block_q:
        raise ValueError(f"chunk C={c} must divide block_q={block_q}")
    scale = 1.0 / (hd**0.5)

    windowed = window is not None
    prefetch = [block_table.astype(jnp.int32), start.astype(jnp.int32)]
    if windowed:
        prefetch.append(window.astype(jnp.int32).reshape(1))

    def page_map(ib, j, iq, ik, bt, st, *win):  # pages past the block's last row
        # (and before its window): the page already held, so no DMA
        first, last = chunk_pages(st[ib] + iq * block_q, block_q, page,
                                  win[0][0] if windowed else None)
        idx = jnp.minimum(ik, last) if not windowed else jnp.clip(ik, first, last)
        return (bt[ib, idx], j, 0, 0)

    def q_map(ib, j, iq, ik, *_):
        return (ib, j, 0, iq, 0)

    kernel = functools.partial(
        _paged_chunk_kernel, scale=scale, page=page, num_page_blocks=n,
        block_q=block_q, groups=g, windowed=windowed,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),  # block_table, start[, window]
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
    )(*prefetch, qh, k_pages, v_pages)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, c, h, hd)
