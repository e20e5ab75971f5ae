"""Per-expert grouped GEMM — Pallas TPU kernel (MegaBlocks-style dense
capacity buffers). [arXiv:2211.15841]

Computes out[e] = xe[e] @ w[e] for every expert with explicit VMEM tiling:
grid = (E, C/bc, F/bf, D/bd); the contraction axis is minor so the (bc, bf)
fp32 accumulator lives in scratch across the d sweep. Block sizes default to
MXU-native 128s; per-expert capacity C is already padded to a multiple of 8
by the MoE layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _gmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, num_d_blocks: int):
    idb = pl.program_id(3)

    @pl.when(idb == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0]  # (bc, bd)
    w = w_ref[0]  # (bd, bf)
    acc_ref[...] += jax.lax.dot_general(
        x, w, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    @pl.when(idb == num_d_blocks - 1)
    def _finalize():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_c", "block_f", "block_d", "interpret"))
def moe_gmm(
    xe: jax.Array,
    w: jax.Array,
    *,
    block_c: int = 128,
    block_f: int = 128,
    block_d: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """xe: (E, C, d); w: (E, d, f) -> (E, C, f)."""
    e, c, d = xe.shape
    f = w.shape[2]
    block_c = min(block_c, c)
    block_f = min(block_f, f)
    block_d = min(block_d, d)
    if c % block_c or f % block_f or d % block_d:
        raise ValueError(f"dims ({c},{f},{d}) must divide blocks ({block_c},{block_f},{block_d})")
    grid = (e, c // block_c, f // block_f, d // block_d)

    kernel = functools.partial(_gmm_kernel, num_d_blocks=d // block_d)
    return pl.pallas_call(
        kernel,
        name="moe_gmm",  # the kernel's HLO op name, whatever jit wraps it
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_c, block_d), lambda ie, ic, if_, id_: (ie, ic, id_)),
            pl.BlockSpec((1, block_d, block_f), lambda ie, ic, if_, id_: (ie, id_, if_)),
        ],
        out_specs=pl.BlockSpec((1, block_c, block_f), lambda ie, ic, if_, id_: (ie, ic, if_)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), xe.dtype),
        scratch_shapes=[pltpu.VMEM((block_c, block_f), jnp.float32)],
        interpret=interpret,
    )(xe, w)
