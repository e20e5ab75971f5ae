"""Phases of the chip smoke run (``chip_smoke.py`` at the repository root).

One pass through the serving main path as a user drives it: ``build_model``
-> :class:`~repro.serving.engine.ServingEngine` on ``TinyJaxBackend`` with
fusion on -> paged KV arena -> :class:`~repro.serving.continuous.
ContinuousBatcher` with chunked prefill. The phases take a config, so the
same code runs at full width on the chip and at ``reduced_config`` size on
the CPU (kernels in interpret mode) as a rehearsal.

What a run checks (:func:`failures`):

* the platform fused the whole chain into one instance while serving, and
  its health check refused no merge;
* the paged decode and paged chunk ops ran their Pallas kernels (counted
  by ``analysis.dispatch.TRACER``), never the gather fallback;
* every request was served its tokens;
* the first-token logits of the checked requests agree with a plain jnp
  forward that calls no Pallas kernel: same argmax, and a largest
  difference within :data:`LOGIT_TOL` of the reference's logit scale;
* where the backend reports memory, the peak stays under
  :data:`HBM_MARGIN` of the device's limit.

The counts it prints are smoke numbers, not benchmark metrics.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dispatch import TRACER
from repro.configs.base import ModelConfig
from repro.core import FusionPolicy, TinyJaxBackend
from repro.kernels import ops, ref
from repro.models import attention as attn_mod
from repro.models.layers import apply_mlp, apply_norm, embed_tokens, unembed
from repro.models.model import build_model
from repro.serving.continuous import ContinuousBatcher
from repro.serving.engine import ServingEngine

#: bf16 tolerance of the reference check: largest |served - reference|
#: first-token logit over the reference's largest |logit|. Both sides run
#: bf16 activations; they differ in attention (online softmax over pages vs
#: one dense softmax), chunking and fusion, each a few bf16 roundings per
#: layer.
LOGIT_TOL = 0.05
#: the printed peak must stay under this share of the device's HBM limit
HBM_MARGIN = 0.9
#: the paged ops whose kernel path the run must show
PAGED_OPS = ("paged_decode_attention", "paged_chunk_attention")
#: invocations a merge is priced over. A smoke run serves a few requests,
#: far fewer than the policy's default horizon; it prices each merge as if
#: the traffic went on, so every hot edge of the chain, the head's
#: included, is due within the run whatever the measured merge stalls.
AMORTIZATION_HORIZON = 10**9

def prompt_lengths(n: int, rng: np.random.Generator, *, page: int, lo: int, hi: int) -> list[int]:
    """``n`` prompt lengths in [lo, hi]. The first is ``lo`` (a multiple of
    the page); the others end less than half a page short of a page
    boundary, so with page-sized chunks every final chunk pads to the same
    full-page program."""
    lens = [lo]
    k_lo, k_hi = -(-(lo + page // 2) // page), hi // page
    for _ in range(n - 1):
        lens.append(int(rng.integers(k_lo, k_hi + 1)) * page - int(rng.integers(0, page // 2)))
    return lens


def build(cfg: ModelConfig, *, seed: int, max_len: int, page: int, num_pages: int):
    """Platform with fusion on, the model's chain deployed on it, and the
    paged arena. Weights come from ``seed``, drawn stage by stage."""
    policy = FusionPolicy(min_observations=2, merge_cost_s=0.0,
                          amortization_horizon=AMORTIZATION_HORIZON)
    platform = TinyJaxBackend(policy)
    engine = ServingEngine(build_model(cfg), platform, max_len=max_len, seed=seed,
                           kv_pages=num_pages, kv_page_size=page)
    return platform, engine


def serve(engine: ServingEngine, prompts: list[np.ndarray], new_tokens: int, *,
          chunk: int, timeout_s: float) -> list[dict]:
    """Submit every prompt to one continuous batcher and wait for all."""
    batcher = ContinuousBatcher(engine, capacity=len(prompts), prefill_chunk=chunk)
    try:
        futs = [batcher.submit({"tokens": jnp.asarray(p[None, :])}, new_tokens) for p in prompts]
        return [f.result(timeout=timeout_s) for f in futs]
    finally:
        batcher.shutdown()


@functools.partial(jax.jit, static_argnums=0)
def _reference_forward(cfg: ModelConfig, embed, groups, head, tokens):
    """Plain jnp forward of the blocks chain on one prompt: dense causal
    softmax attention (``kernels/ref.py``), no paging, no chunking, no
    Pallas. Returns the last position's logits (1, V)."""
    positions = jnp.arange(tokens.shape[1])[None, :]

    def layer(x, p):
        h = apply_norm(p["ln1"], x, cfg)
        q, k, v = attn_mod.qkv_project(p["attn"], h, cfg, positions)
        x = x + attn_mod.attn_output(p["attn"], ref.mha_ref(q, k, v, causal=True))
        return x + apply_mlp(p["mlp"], apply_norm(p["ln2"], x, cfg), cfg), None

    x = embed_tokens(embed, tokens)
    for g in groups:
        x, _ = jax.lax.scan(layer, x, g)
    return unembed(head["embed"], apply_norm(head["ln_f"], x[:, -1:], cfg))[:, 0]


def reference_logits(engine: ServingEngine, tokens: np.ndarray) -> jax.Array:
    """First-token logits of the reference forward, on the weights the
    deployed functions hold (the only copy there is)."""
    spec = engine.platform.spec_of
    groups = [spec(n).params for n in engine.group_names]
    return _reference_forward(engine.cfg, spec(engine.entry).params, groups,
                              spec(engine.chain_names()[-1]).params,
                              jnp.asarray(tokens[None, :]))


def compare(served: jax.Array, expect: jax.Array) -> dict:
    s = np.asarray(served, np.float32).reshape(-1)
    e = np.asarray(expect, np.float32).reshape(-1)
    diff = float(np.max(np.abs(s - e)))
    scale = float(np.max(np.abs(e)))
    return {"max_abs_diff": diff, "ref_scale": scale, "rel": diff / max(scale, 1e-30),
            "argmax_served": int(np.argmax(s)), "argmax_ref": int(np.argmax(e))}


def run(cfg: ModelConfig, *, seed: int = 0, requests: int = 8, new_tokens: int = 32,
        page: int = 128, min_prompt: int = 256, max_prompt: int = 1024,
        check: int = 2, timeout_s: float = 900.0) -> dict:
    """Serve ``requests`` seeded prompts end to end and check them. Returns
    the report :func:`failures` and ``chip_smoke.py`` read."""
    rng = np.random.default_rng(seed)
    lens = prompt_lengths(requests, rng, page=page, lo=min_prompt, hi=max_prompt)
    prompts = [rng.integers(0, cfg.vocab_size, (n,), dtype=np.int32) for n in lens]
    max_len = -(-(max(lens) + new_tokens) // page) * page
    # the arena holds every request at once (+ the reserved scratch page)
    num_pages = 1 + sum(-(-(n + new_tokens) // page) for n in lens)

    TRACER.arm()
    snap0, paths0 = TRACER.snapshot(), TRACER.path_counts()
    t0 = time.perf_counter()
    platform, engine = build(cfg, seed=seed, max_len=max_len, page=page, num_pages=num_pages)
    try:
        jax.block_until_ready([platform.spec_of(n).params for n in engine.chain_names()])
        t_build = time.perf_counter() - t0
        t1 = time.perf_counter()
        results = serve(engine, prompts, new_tokens, chunk=page, timeout_s=timeout_s)
        t_serve = time.perf_counter() - t1
        paths = {f"{op}:{path}": n - paths0.get((op, path), 0)
                 for (op, path), n in sorted(TRACER.path_counts().items())}
        checks = [
            dict(compare(results[i]["first_logits"], reference_logits(engine, prompts[i])),
                 request=i, prompt_len=lens[i])
            for i in range(min(check, requests))
        ]
        compiled = TRACER.delta(snap0)
        stats = platform.stats()
        merges = [m["members"] for m in stats["merges"] if m["healthy"]]
        refused = [(m["members"], f"{m['reason']}: {m['detail']}" if m["detail"] else m["reason"])
                   for m in stats["merges"] if not m["healthy"]]
        mem = jax.devices()[0].memory_stats() or {}
        return {
            "dispatch_mode": ops.dispatch_mode(),
            "prompt_lens": lens,
            "new_tokens": new_tokens,
            "page": page,
            "arena_pages": num_pages,
            "max_len": max_len,
            "tokens_served": [int(r["tokens"].shape[1]) for r in results],
            "kernel_paths": paths,
            "merges": merges,
            "merges_refused": refused,
            "instances_left": len(stats["instances"]),
            "checks": checks,
            "build_s": t_build,
            "serve_s": t_serve,
            "compiles": compiled.compiles,
            "compile_s": compiled.compile_s,
            "peak_bytes": mem.get("peak_bytes_in_use"),
            "bytes_limit": mem.get("bytes_limit"),
        }
    finally:
        TRACER.disarm()
        platform.shutdown()


def failures(report: dict) -> list[str]:
    """Every reason the run does not pass; empty when it does."""
    out = []
    if not report["merges"]:
        out.append("no chain merge happened")
    elif report["instances_left"] != 1:
        out.append(f"the chain was left in {report['instances_left']} instances, not fused to one")
    for members, reason in report["merges_refused"]:
        out.append(f"merge of {' + '.join(members)} refused: {reason}")
    paths = report["kernel_paths"]
    kernel = "interpret" if report["dispatch_mode"] == "interpret" else "kernel"
    for op in PAGED_OPS:
        if not paths.get(f"{op}:{kernel}"):
            out.append(f"{op} never took the {kernel} path")
        if paths.get(f"{op}:ref"):
            out.append(f"{op} took the gather fallback")
    if any(n != report["new_tokens"] for n in report["tokens_served"]):
        out.append(f"tokens served {report['tokens_served']} != {report['new_tokens']} each")
    for c in report["checks"]:
        if c["argmax_served"] != c["argmax_ref"]:
            out.append(f"request {c['request']}: argmax {c['argmax_served']} != reference {c['argmax_ref']}")
        if not c["rel"] <= LOGIT_TOL:
            out.append(f"request {c['request']}: logit diff {c['max_abs_diff']} is "
                       f"{c['rel']} of the reference scale > {LOGIT_TOL}")
    peak, limit = report["peak_bytes"], report["bytes_limit"]
    if peak is not None and limit and peak > HBM_MARGIN * limit:
        out.append(f"peak {peak} B above {HBM_MARGIN} of the {limit} B limit")
    return out
