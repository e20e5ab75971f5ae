"""Kernel micro-benchmarks: jnp oracle wall-time on this host (CPU) as the
throughput reference + interpret-mode validation deltas. (TPU wall-times are
not measurable here; the dry-run roofline covers projected TPU perf.)"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _time(fn, *args, iters: int = 5) -> float:
    fn(*args)  # warm/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters * 1e6


def run() -> list[dict]:
    rng = jax.random.PRNGKey(0)
    rows = []

    b, t, h, kv, hd = 1, 512, 8, 2, 64
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)
    us = _time(jax.jit(lambda a, b2, c: ref.mha_ref(a, b2, c, causal=True)), q, k, v)
    rows.append({"name": "mha_ref_512x8h", "us_per_call": round(us, 1)})

    s = 2048
    kd = jax.random.normal(ks[1], (2, s, kv, hd), jnp.float32)
    vd = jax.random.normal(ks[2], (2, s, kv, hd), jnp.float32)
    qd = jax.random.normal(ks[0], (2, h, hd), jnp.float32)
    cur = jnp.array([s, s // 2])
    us = _time(jax.jit(lambda a, b2, c, d: ref.decode_attn_ref(a, b2, c, d)), qd, kd, vd, cur)
    rows.append({"name": "decode_attn_ref_2k", "us_per_call": round(us, 1)})

    from repro.models.ssm import ssd_chunked

    x = jax.random.normal(ks[0], (1, 512, 8, 32), jnp.float32)
    bm = jax.random.normal(ks[1], (1, 512, 1, 16), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (1, 512, 1, 16), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[0], (1, 512, 8), jnp.float32))
    al = jnp.zeros((8,))
    dk = jnp.ones((8,))
    us = _time(jax.jit(lambda *a: ssd_chunked(*a, chunk=128)[0]), x, bm, cm, dt, al, dk)
    rows.append({"name": "ssd_chunked_512", "us_per_call": round(us, 1)})

    xe = jax.random.normal(ks[0], (8 * 128, 128), jnp.float32)
    w = jax.random.normal(ks[1], (8, 128, 256), jnp.float32) * 0.05
    us = _time(jax.jit(ref.gmm_ref), xe, w, jnp.full((8,), 128, jnp.int32))
    rows.append({"name": "moe_gmm_ref_8x128", "us_per_call": round(us, 1)})
    return rows
