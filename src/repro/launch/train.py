"""Training launcher.

CPU-scale driver for real runs in this container; the same entry point
drives a pod by passing --mesh (the mesh/sharding machinery is identical —
see dryrun.py for the 256/512-chip lowering proof).

  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --reduced \
      --steps 200 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true", help="tiny same-family config (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mode", default="affine", choices=["affine", "random"])
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import dataclasses

    import jax

    from repro.checkpointing import CheckpointManager
    from repro.configs import get_arch, reduced_config
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticTokenPipeline
    from repro.launch.compile_cache import enable_persistent_cache
    from repro.models.model import build_model

    # persistent XLA cache across train relaunches
    enable_persistent_cache()
    from repro.optim import AdamWConfig, cosine_schedule
    from repro.training import TrainLoop
    from repro.training.train_step import init_train_state, make_train_step

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    if args.microbatches > 1:
        cfg = dataclasses.replace(cfg, microbatches=args.microbatches)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    model = build_model(cfg)
    step_fn = make_train_step(
        model, AdamWConfig(lr=args.lr), cosine_schedule(args.lr, max(1, args.steps // 10), args.steps)
    )
    state = init_train_state(model, jax.random.PRNGKey(0))
    manager = CheckpointManager(args.ckpt_dir, retain=3, async_save=True)
    loop = TrainLoop(
        step_fn,
        lambda start: SyntheticTokenPipeline(cfg, shape, seed=0, mode=args.mode, start_batch=start),
        manager,
        ckpt_every=args.ckpt_every,
    )
    t0 = time.perf_counter()
    state, history = loop.run(state, args.steps)
    wall = time.perf_counter() - t0
    for h in history[:: args.log_every]:
        print(f"step {h['step']:5d} loss {h['loss']:.4f} {h['seconds']*1e3:.0f}ms")
    tokens = args.steps * args.batch * args.seq
    print(json.dumps({
        "arch": cfg.name, "steps": args.steps, "wall_s": round(wall, 1),
        "tokens_per_s": round(tokens / wall, 1),
        "final_loss": round(history[-1]["loss"], 4),
        "first_loss": round(history[0]["loss"], 4),
        "stragglers": len(loop.straggler_events),
    }))


if __name__ == "__main__":
    main()
