"""Grouped GEMM over rows sorted by expert — Pallas TPU kernel (the ragged,
dropless layout of MegaBlocks; JAX's bundled megablox ``gmm`` takes group
sizes by scalar prefetch the same way). [arXiv:2211.15841]

``x`` holds each expert's rows one after another, every group padded to
whole blocks of ``block_rows`` rows, so each row block belongs to one
expert. The grid walks the row blocks; the block's expert comes from the
scalar-prefetched group ids, and its whole ``(d, f)`` weight panel is the
weight block. Consecutive blocks of one expert map to the same weight
block, which Pallas does not fetch again, so each expert's weights are
read once per call, and an expert with no rows is never fetched. Blocks
past the last group's end map to the last block in use: they start no
DMA and compute nothing, so their output rows are left unwritten.

``w`` may also be a stack of layers' experts, (L, E, d, f), with the layer
a third scalar-prefetched operand: the kernel then reads that layer's
panels in place, and the caller never slices a (E, d, f) bank out of the
stack (a decode step's scan would copy every layer's whole bank for it).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: VMEM for the double-buffered weight panel (2 x 4.1 MB at Mellum2's
#: 2304 x 896) with the row blocks beside it
VMEM_LIMIT_BYTES = 48 << 20


def block_groups(group_sizes: jax.Array, block_rows: int, num_blocks: int):
    """(expert of each row block, row blocks in use (1,)) for groups of
    ``group_sizes`` rows, each a whole number of ``block_rows`` blocks.
    A block past the groups' end gets the last group's id."""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    starts = jnp.arange(num_blocks, dtype=jnp.int32) * block_rows
    ids = jnp.searchsorted(ends, starts, side="right").astype(jnp.int32)
    return jnp.minimum(ids, group_sizes.shape[0] - 1), (ends[-1:] // block_rows)


def _live(i, active):
    """The row block grid step ``i`` reads: itself while in use, else the
    last block in use (no new DMA)."""
    return jnp.minimum(i, jnp.maximum(active[0] - 1, 0))


def _gmm_kernel(ids_ref, active_ref, *refs):
    x_ref, w_ref, o_ref = refs[-3:]  # after the layer's ref, when stacked

    @pl.when(pl.program_id(0) < active_ref[0])
    def _():
        o_ref[...] = jax.lax.dot_general(
            x_ref[...], w_ref[0], (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def moe_gmm(
    x: jax.Array,
    w: jax.Array,
    group_sizes: jax.Array,
    layer: jax.Array | None = None,
    *,
    block_rows: int,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, d) rows sorted by expert; w: (E, d, f), or (L, E, d, f) with
    ``layer`` (1,) int32 naming the layer; group_sizes: (E,) int32, each a
    multiple of ``block_rows`` -> (M, f). Rows past the groups' sum are not
    written."""
    m, d = x.shape
    f = w.shape[-1]
    if m % block_rows:
        raise ValueError(f"rows M={m} must be whole blocks of {block_rows}")
    nb = m // block_rows
    ids, active = block_groups(group_sizes, block_rows, nb)
    prefetch = (ids, active) if layer is None else (ids, active, layer)

    def rows(i, ids, active, *_):
        return (_live(i, active), 0)

    if layer is None:
        panel = pl.BlockSpec((1, d, f), lambda i, ids, active: (ids[_live(i, active)], 0, 0))
    else:
        panel = pl.BlockSpec((None, 1, d, f),
                             lambda i, ids, active, layer: (layer[0], ids[_live(i, active)], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        # expert of each row block, blocks in use, and the layer if stacked
        num_scalar_prefetch=len(prefetch),
        grid=(nb,),
        in_specs=[pl.BlockSpec((block_rows, d), rows), panel],
        out_specs=pl.BlockSpec((block_rows, f), rows),
    )
    return pl.pallas_call(
        _gmm_kernel,
        name="moe_gmm",  # the kernel's HLO op name, whatever jit wraps it
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, f), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(*prefetch, x, w)
