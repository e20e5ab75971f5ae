"""Causal GQA flash attention — Pallas TPU kernel.

Tiling: grid = (B, H, T/bq, S/bk). TPU executes the grid sequentially with
the last axis minor, so the kv index ``ik`` sweeps fully for each q block
``iq``; the online-softmax running state (m, l, acc) lives in VMEM scratch
and is carried across the ``ik`` sweep [FlashAttention, arXiv:2205.14135,
re-tiled for the MXU: bq = bk = 128 and head_dim-sized accumulators].

Layout: the kernel reads head-major views, q (B, H, T, hd) and k/v
(B, KV, S, hd), with the head axis squeezed out of every block, so each
block's last two dims are (rows, hd) — the (8k, 128) tiles the TPU compiler
requires. GQA: the index maps route q head ``h`` to kv head ``h // G``, so
grouped heads reuse the same K/V block stream (no replication in HBM).

Causality: blocks fully above the diagonal are skipped (no compute, and the
index map re-points them at the last needed block so no DMA is issued); the
diagonal block applies the per-element triangular mask.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, causal: bool, scale: float, block_q: int, block_k: int, num_k_blocks: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def _body():
        s = osm.scores(q_ref[...], k_ref[...], scale)  # (bq, bk)
        if causal:
            rows = iq * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
            cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, osm.NEG_INF)
        m_ref[...], l_ref[...], acc_ref[...] = osm.step(
            s, v_ref[...], m_ref[...], l_ref[...], acc_ref[...]
        )

    if causal:
        pl.when(ik * block_k <= iq * block_q + block_q - 1)(_body)
    else:
        _body()

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        o_ref[...] = osm.finalize(l_ref[...], acc_ref[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret"))
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, T, H, hd); k, v: (B, S, KV, hd) -> (B, T, H, hd)."""
    b, t, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_q = min(block_q, t)
    block_k = min(block_k, s)
    if t % block_q or s % block_k:
        raise ValueError(f"seq lengths ({t},{s}) must divide blocks ({block_q},{block_k})")
    grid = (b, h, t // block_q, s // block_k)
    scale = 1.0 / (hd**0.5)

    def kv_map(ib, ih, iq, ik):
        if causal:  # above-diagonal blocks re-read the last needed one (no DMA)
            ik = jnp.minimum(ik, (iq * block_q + block_q - 1) // block_k)
        return (ib, ih // g, ik, 0)

    kernel = functools.partial(
        _flash_kernel,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        num_k_blocks=s // block_k,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",  # the kernel's HLO op name, whatever jit wraps it
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, block_q, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, block_q, hd), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, t, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # m: running row max
            pltpu.VMEM((block_q, 1), jnp.float32),    # l: running row sum
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc: unnormalized output
        ],
        interpret=interpret,
    )(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    return jnp.swapaxes(out, 1, 2)
