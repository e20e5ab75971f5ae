"""Serving launcher: deploy a model as a Provuse function chain and serve a
batched request stream, reporting per-token latency before/after the
platform's automatic fusion.

  PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b --reduced \
      --backend tinyjax --requests 64 --tokens 16
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--backend", default="tinyjax", choices=["tinyjax", "orchestrated"])
    ap.add_argument("--no-fusion", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--min-observations", type=int, default=2)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch, reduced_config
    from repro.core import FusionPolicy, OrchestratedBackend, TinyJaxBackend
    from repro.launch.compile_cache import enable_persistent_cache
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine

    # persistent XLA cache — relaunches restore executables instead of
    # rebuilding them (the cold-start story)
    enable_persistent_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg)
    Backend = TinyJaxBackend if args.backend == "tinyjax" else OrchestratedBackend
    policy = FusionPolicy(min_observations=args.min_observations, merge_cost_s=0.0, enabled=not args.no_fusion)
    platform = Backend(policy)
    engine = ServingEngine(model, platform, max_len=args.max_len)

    rng = np.random.default_rng(0)
    if cfg.family == "audio":
        inputs = {
            "src_embeds": jnp.asarray(rng.standard_normal((args.batch, args.prompt_len, cfg.d_model)) * 0.02, jnp.bfloat16),
            "tokens": jnp.zeros((args.batch, 1), jnp.int32),
        }
    elif cfg.family == "vlm":
        inputs = {"embeds": jnp.asarray(rng.standard_normal((args.batch, args.prompt_len, cfg.d_model)) * 0.02, jnp.bfloat16)}
    else:
        inputs = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)), jnp.int32)}

    t0 = time.perf_counter()
    toks, lat = engine.generate(inputs, steps=args.tokens)
    wall = time.perf_counter() - t0
    stats = platform.stats()
    merges = [m for m in stats["merges"] if m["healthy"]]
    pre = float(np.median(lat[:3])) if len(lat) >= 3 else float("nan")
    post = float(np.median(lat[-3:])) if len(lat) >= 3 else float("nan")
    print(json.dumps({
        "arch": cfg.name,
        "backend": platform.backend_name,
        "fusion": not args.no_fusion,
        "generated": list(map(int, np.asarray(toks[0])[:8])),
        "merges": [list(m["members"]) for m in merges],
        "per_token_ms_pre": round(pre * 1e3, 2),
        "per_token_ms_post": round(post * 1e3, 2),
        "instances_left": len(stats["instances"]),
        "ram_bytes": stats["ram_bytes"],
        "billing_gb_s": round(stats["billing"]["total_gb_s"], 6),
        "wall_s": round(wall, 2),
    }, indent=2))
    platform.shutdown()


if __name__ == "__main__":
    main()
