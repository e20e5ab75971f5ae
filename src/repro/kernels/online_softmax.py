"""The online-softmax step shared by every attention kernel.

Each kernel sweeps key blocks on the minor grid axis and carries, per query
row, the running max ``m`` (R, 1), the running denominator ``l`` (R, 1) and
the unnormalized output ``acc`` (R, hd) in VMEM scratch. All values are 2-D:
the TPU compiler lays out (sublane, lane) tiles and refuses 1-D vectors.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """q (R, hd) x k (K, hd) -> (R, K) f32 scores on the MXU."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    return s * scale


def step(s, v, m_prev, l_prev, acc_prev):
    """Fold one masked score block ``s`` (R, K) and its values ``v`` (K, hd)
    into the running state. Masked positions (``NEG_INF``) contribute an
    explicit zero: a row whose every score so far is masked keeps ``l == 0``
    and finalizes to exact zeros instead of a mean over padding."""
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur))
    l_cur = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    return m_cur, l_cur, acc_prev * alpha + pv


def finalize(l, acc, dtype):
    return (acc / jnp.where(l == 0.0, 1.0, l)).astype(dtype)
