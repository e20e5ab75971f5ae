"""Flash-decoding attention — Pallas TPU kernel for the dense-cache serve path.

One query token per sequence against a long KV cache. Tiling:
grid = (B, KV, S/bk); the kv sweep is the minor axis, so the partial-softmax
state (m, l, acc) is carried in VMEM scratch across kv blocks — the split-K
decode schedule of FlashDecoding [arXiv:2311.01282] mapped onto the TPU's
sequential grid. One block holds the G query heads that share a KV head, so
each K/V block is fetched once per group and the scores are one (G, bk) MXU
matmul. Valid-length masking comes from the per-sequence ``cur_len`` vector
(scalar-prefetched; continuous batching gives each request its own length);
blocks past it are skipped.

Layout: the cache is read head-major, (B, KV, S, hd), so each block's last
two dims are (bk, hd) — the tiling the TPU compiler accepts. The caller's
(B, S, KV, hd) cache is transposed on the way in; the paged arena
(``paged_attention.py``) stores pages head-major and needs no transpose.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import online_softmax as osm

DEFAULT_BLOCK_K = 512


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *, scale: float, block_k: int, num_k_blocks: int):
    ib, ik = pl.program_id(0), pl.program_id(2)
    cur = len_ref[ib]

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, osm.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(ik * block_k < cur)
    def _body():
        s = osm.scores(q_ref[...], k_ref[...], scale)  # (G, bk)
        cols = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < cur, s, osm.NEG_INF)
        m_ref[...], l_ref[...], acc_ref[...] = osm.step(
            s, v_ref[...], m_ref[...], l_ref[...], acc_ref[...]
        )

    @pl.when(ik == num_k_blocks - 1)
    def _finalize():
        o_ref[...] = osm.finalize(l_ref[...], acc_ref[...], o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    cur_len: jax.Array,
    *,
    block_k: int = DEFAULT_BLOCK_K,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, H, hd); k, v: (B, S, KV, hd); cur_len: (B,) -> (B, H, hd)."""
    b, h, hd = q.shape
    s, kv = k.shape[1], k.shape[2]
    g = h // kv
    block_k = min(block_k, s)
    if s % block_k:
        raise ValueError(f"S={s} must divide block_k={block_k}")
    scale = 1.0 / (hd**0.5)

    def kv_map(ib, j, ik, ln):  # blocks past cur_len re-read the last live one
        return (ib, j, jnp.minimum(ik, jnp.maximum(ln[ib] - 1, 0) // block_k), 0)

    kernel = functools.partial(
        _decode_kernel, scale=scale, block_k=block_k, num_k_blocks=s // block_k
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,  # cur_len
        grid=(b, kv, s // block_k),
        in_specs=[
            pl.BlockSpec((None, None, g, hd), lambda ib, j, ik, ln: (ib, j, 0, 0)),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
            pl.BlockSpec((None, None, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((None, None, g, hd), lambda ib, j, ik, ln: (ib, j, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",  # the kernel's HLO op name, whatever jit wraps it
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        interpret=interpret,
    )(cur_len.astype(jnp.int32), q.reshape(b, kv, g, hd),
      jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    return out.reshape(b, h, hd)
