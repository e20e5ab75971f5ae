#!/usr/bin/env python3
"""Chip smoke run: StarCoder2-3B at its full published widths and depth,
served on one TPU through the platform's main path.

    python3 chip_smoke.py [--seed N]

Builds the model with weights drawn from ``--seed``, deploys it as a
function chain with fusion on, pages its KV cache in 128-token pages and
serves eight seeded prompts of 256-1024 tokens, 32 generated tokens each,
through the continuous batcher with chunked prefill. The phases live in
``src/repro/launch/smoke.py``, where ``tests/test_smoke.py`` rehearses
them on the CPU at reduced size.

It prints the device, the kernel dispatch mode, the op paths the dispatch
tracer saw, the chain merges, the reference check, compile seconds, peak
device memory and the tokens served. The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check passed.
Without a TPU it exits non-zero and prints no result: it never falls back
to another backend. Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0, help="weights and prompts seed")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {dev.platform!r})", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.configs import get_arch
    from repro.launch import smoke
    from repro.launch.compile_cache import enable_persistent_cache

    print(f"device: {dev.platform} {dev.device_kind} (x{len(jax.devices())})")
    print(f"compile cache: {enable_persistent_cache()}")
    cfg = get_arch("starcoder2-3b")
    report = smoke.run(cfg, seed=args.seed)

    print(f"dispatch_mode: {report['dispatch_mode']}")
    print(f"prompt lengths: {report['prompt_lens']} (+{report['new_tokens']} generated, "
          f"page {report['page']}, arena {report['arena_pages']} pages, max_len {report['max_len']})")
    print(f"op paths (op:path -> entries): {report['kernel_paths']}")
    for members in report["merges"]:
        print(f"merge: {' + '.join(members)}")
    for members, reason in report["merges_refused"]:
        print(f"merge refused: {' + '.join(members)} ({reason})")
    print(f"instances left: {report['instances_left']}")
    for c in report["checks"]:
        print(f"reference check: request {c['request']} (prompt {c['prompt_len']}): "
              f"max |served - reference| = {c['max_abs_diff']} on logit scale {c['ref_scale']} "
              f"(rel {c['rel']}, tol {smoke.LOGIT_TOL}); argmax {c['argmax_served']} vs "
              f"{c['argmax_ref']}")
    print(f"compile: {report['compiles']} programs, {report['compile_s']} s")
    print(f"build {report['build_s']} s, serve {report['serve_s']} s")
    print(f"peak HBM: {report['peak_bytes']} B of {report['bytes_limit']} B")
    print(f"tokens served: {sum(report['tokens_served'])} ({report['tokens_served']})")

    failed = smoke.failures(report)
    if failed:
        for f in failed:
            print(f"FAILED: {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
