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


def attention(q, k, v, *, causal: bool = True):
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) -> (B, T, H, hd)."""
    mode = _dispatch("attention", q)
    if mode == "ref":
        return ref.chunked_mha_ref(q, k, v, causal=causal)
    return _flash_kernel(q, k, v, causal=causal, interpret=(mode == "interpret"))


def decode_attention(q, k, v, cur_len):
    """q: (B, H, hd); k, v: (B, S, KV, hd); cur_len: (B,) -> (B, H, hd)."""
    mode = _dispatch("decode_attention", q)
    if mode == "ref":
        return ref.decode_attn_ref(q, k, v, cur_len)
    return _decode_kernel(q, k, v, cur_len, interpret=(mode == "interpret"))


def paged_decode_attention(q, k_pages, v_pages, block_table, cur_len):
    """q: (B, H, hd); pages (P, KV, page, hd); block_table (B, n) int32.

    The block-table-indirect split-K kernel, or off the TPU one gather that
    rebuilds the contiguous (B, n*page, KV, hd) view and the dense decode
    reference on it — the same program on the same values as a dense cache
    of that width (masked positions contribute exactly zero), so paging is
    bit-exact there."""
    mode = _dispatch("paged_decode_attention", q)
    if mode == "ref":
        k, v = _gathered(k_pages, block_table, q.dtype), _gathered(v_pages, block_table, q.dtype)
        return ref.decode_attn_ref(q, k, v, cur_len)
    return _paged_kernel(q, k_pages, v_pages, block_table, cur_len,
                         interpret=(mode == "interpret"))


def paged_chunk_attention(q, k_pages, v_pages, block_table, start):
    """q: (1, C, H, hd) chunk rows from absolute position ``start`` (1,);
    chunked-prefill attention over the page arena.

    The chunk kernel, or off the TPU one gather and the q-chunked reference
    with ``q_offset=start`` — bit-exact against a dense causal prefill."""
    mode = _dispatch("paged_chunk_attention", q)
    if mode == "ref":
        k, v = _gathered(k_pages, block_table, q.dtype), _gathered(v_pages, block_table, q.dtype)
        return ref.chunked_mha_ref(q, k, v, causal=True, q_offset=start[0])
    return _paged_chunk_kernel(q, k_pages, v_pages, block_table, start,
                               interpret=(mode == "interpret"))


def gmm(xe, w):
    """Per-expert GEMM of every routing group. xe: (G, E, C, d); w: (E, d, f)
    -> (G, E, C, f). The kernel runs once per group."""
    mode = _dispatch("gmm", xe)
    if mode == "ref":
        return jnp.einsum("gecd,edf->gecf", xe, w)
    return jnp.stack([_gmm_kernel(x, w, interpret=(mode == "interpret")) for x in xe])
