"""Mamba-2 SSD chunked scan — Pallas TPU kernel. [arXiv:2405.21060]

Tiling: grid = (B, H, T/Q). The chunk axis is minor, so the inter-chunk
recurrent state (P, N) is carried in VMEM scratch across chunks of one
(batch, head) stream. Per chunk the kernel does the SSD dual form:
three (Q x Q)/(Q x N)/(Q x P) MXU matmuls for the intra-chunk part, one
rank-Q update for the state — this is the TPU-native re-blocking of the
CUDA chunk kernel in the paper (VMEM-resident decay matrices; chunk Q is
chosen 128-multiple so every matmul hits the MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, b_ref, c_ref, dt_ref, alog_ref, d_ref, y_ref, state_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, :, 0, :].astype(jnp.float32)  # (Q, P)
    bm = b_ref[0, :, 0, :].astype(jnp.float32)  # (Q, N)
    cm = c_ref[0, :, 0, :].astype(jnp.float32)  # (Q, N)
    dt = dt_ref[0, :, 0].astype(jnp.float32)  # (Q,)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))
    d_skip = d_ref[0].astype(jnp.float32)

    dta = dt * a
    cum = jnp.cumsum(dta)  # (Q,)
    li = cum[:, None] - cum[None, :]
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    decay = jnp.where(rows >= cols, jnp.exp(li), 0.0)
    lmat = decay * dt[None, :]

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    scores = scores * lmat  # (Q, Q)
    y_intra = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    state = state_ref[...]  # (P, N)
    y_inter = jax.lax.dot_general(cm, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    y_inter = y_inter * jnp.exp(cum)[:, None]  # (Q, P)

    y = y_intra + y_inter + x * d_skip
    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    total = cum[chunk - 1]
    w_j = jnp.exp(total - cum) * dt  # (Q,)
    ds = jax.lax.dot_general(
        (x * w_j[:, None]), bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )  # (P, N)
    state_ref[...] = state * jnp.exp(total) + ds


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,
    bm: jax.Array,
    cm: jax.Array,
    dt: jax.Array,
    a_log: jax.Array,
    d_skip: jax.Array,
    *,
    chunk: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """x: (B,T,H,P); bm/cm: (B,T,G,N); dt: (B,T,H); a_log/d_skip: (H,)
    -> y: (B,T,H,P) (fp32 accumulated, cast to x.dtype)."""
    b, t, h, p = x.shape
    grp, n = bm.shape[2], bm.shape[3]
    hpg = h // grp
    chunk = min(chunk, t)
    if t % chunk:
        raise ValueError(f"T={t} must divide chunk={chunk}")
    grid = (b, h, t // chunk)

    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        name="ssd_scan",  # the kernel's HLO op name, whatever jit wraps it
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda ib, ih, ic, hpg=hpg: (ib, ic, ih // hpg, 0)),
            pl.BlockSpec((1, chunk, 1, n), lambda ib, ih, ic, hpg=hpg: (ib, ic, ih // hpg, 0)),
            pl.BlockSpec((1, chunk, 1), lambda ib, ih, ic: (ib, ic, ih)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
            pl.BlockSpec((1,), lambda ib, ih, ic: (ih,)),
        ],
        out_specs=pl.BlockSpec((1, chunk, 1, p), lambda ib, ih, ic: (ib, ic, ih, 0)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, bm, cm, dt, a_log, d_skip)
