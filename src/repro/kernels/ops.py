"""Dispatch wrappers: Pallas kernel on TPU, jnp oracle elsewhere.

The models call these entry points, and only these decide between a
kernel and its reference. On a TPU every op runs its Pallas kernel,
always: a shape the kernel's blocks do not divide raises from the kernel
wrapper with the shapes, it never drops to the oracle. Off the TPU (tests,
dry-run lowering) the ops take the jnp references of ``kernels/ref.py``,
and ``REPRO_USE_PALLAS=1`` runs the kernels in interpret mode instead
(kernel parity tests, the CPU rehearsal of ``chip_smoke.py``).

Every entry is reported to ``analysis.dispatch.TRACER`` with the path it
took, so a run can show that the device path really ran.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.analysis.dispatch import TRACER
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as _decode_kernel
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.moe_gmm import moe_gmm as _gmm_kernel
from repro.kernels.paged_attention import gather_pages
from repro.kernels.paged_attention import paged_chunk_attention as _paged_chunk_kernel
from repro.kernels.paged_attention import paged_decode_attention as _paged_kernel


def _mode() -> str:
    """'kernel' | 'interpret' | 'ref'."""
    if jax.default_backend() == "tpu":
        return "kernel"
    if os.environ.get("REPRO_USE_PALLAS", "") == "1":
        return "interpret"
    return "ref"


def dispatch_mode() -> str:
    """Public view of the kernel dispatch mode: 'kernel' | 'interpret' |
    'ref'. Part of every executable-cache key (``launch/compile_cache``):
    the same entry lowers to a different program per mode, so a mode flip
    must miss the cache rather than reuse a stale lowering."""
    return _mode()


def _dispatch(name: str, probe) -> str:
    mode = _mode()
    TRACER.note_kernel_call(name, probe, path=mode)
    return mode


def _gathered(pages, block_table, dtype):
    out = gather_pages(pages, block_table)
    return out.astype(dtype) if out.dtype != dtype else out


def _no_window(name: str, window) -> None:
    """The dense-cache kernels have no window: a windowed model serves from
    the paged kernels, and its dense route runs off the TPU only."""
    if window is not None:
        raise ValueError(f"{name}: the kernel has no sliding window; a windowed "
                         f"model serves through the paged kernels")


def attention(q, k, v, *, causal: bool = True, window=None):
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) -> (B, T, H, hd); ``window``
    (1,) limits each causal row to its last keys."""
    mode = _dispatch("attention", q)
    if mode == "ref":
        return ref.chunked_mha_ref(q, k, v, causal=causal, window=window)
    _no_window("attention", window)
    return _flash_kernel(q, k, v, causal=causal, interpret=(mode == "interpret"))


def decode_attention(q, k, v, cur_len, window=None):
    """q: (B, H, hd); k, v: (B, S, KV, hd); cur_len: (B,) -> (B, H, hd)."""
    mode = _dispatch("decode_attention", q)
    if mode == "ref":
        return ref.decode_attn_ref(q, k, v, cur_len, window)
    _no_window("decode_attention", window)
    return _decode_kernel(q, k, v, cur_len, interpret=(mode == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, block_table, cur_len, window=None):
    """q: (B, H, hd); pages (P, KV, page, hd); block_table (B, n) int32;
    ``window`` (1,) or None.

    The block-table-indirect split-K kernel, or off the TPU one gather that
    rebuilds the contiguous (B, n*page, KV, hd) view and the dense decode
    reference on it — the same program on the same values as a dense cache
    of that width (masked positions contribute exactly zero), so paging is
    bit-exact there."""
    mode = _dispatch("paged_decode_attention", q)
    if mode == "ref":
        k, v = _gathered(k_pages, block_table, q.dtype), _gathered(v_pages, block_table, q.dtype)
        return ref.decode_attn_ref(q, k, v, cur_len, window)
    return _paged_kernel(q, k_pages, v_pages, block_table, cur_len, window,
                         interpret=(mode == "interpret"))


def paged_chunk_attention(q, k_pages, v_pages, block_table, start, window=None):
    """q: (1, C, H, hd) chunk rows from absolute position ``start`` (1,);
    chunked-prefill attention over the page arena; ``window`` (1,) or None.

    The chunk kernel, or off the TPU one gather and the q-chunked reference
    with ``q_offset=start`` — bit-exact against a dense causal prefill."""
    mode = _dispatch("paged_chunk_attention", q)
    if mode == "ref":
        k, v = _gathered(k_pages, block_table, q.dtype), _gathered(v_pages, block_table, q.dtype)
        return ref.chunked_mha_ref(q, k, v, causal=True, q_offset=start[0], window=window)
    return _paged_chunk_kernel(q, k_pages, v_pages, block_table, start, window,
                               interpret=(mode == "interpret"))


def gmm(x, w, group_sizes, layer=None, *, block_rows: int):
    """Grouped GEMM of expert rows. x: (M, d) rows sorted by expert, each
    expert's ``group_sizes`` (E,) rows a whole number of ``block_rows``
    blocks; w: (E, d, f), or a stack (L, E, d, f) read in place at
    ``layer`` (1,) int32 (reported as ``gmm_stacked``) -> (M, f). Rows past
    the groups are not read back by the caller (the kernel leaves them
    unwritten, the reference zero)."""
    mode = _dispatch("gmm" if layer is None else "gmm_stacked", x)
    if mode == "ref":
        return ref.gmm_ref(x, w, group_sizes, layer)
    return _gmm_kernel(x, w, group_sizes, layer, block_rows=block_rows,
                       interpret=(mode == "interpret"))
