"""Open/closed-loop load generator for the Provuse request scheduler.

Drives concurrent decode traffic through a ServingEngine chain and measures
throughput + tail latency under four regimes: {unfused, fused} x {serial
`invoke`, micro-batched `invoke_async`}. The headline comparison (fused
chain, batched vs serial dispatch at --concurrency 8) is the scheduler's
reason to exist: the paper's fusion makes one request faster; the scheduler
makes the fused unit serve many at once.

Closed loop (default): C client threads, each with its own KV cache, decode
as fast as responses return for --steps iterations.
Open loop (--rate R): a single generator submits `invoke_async` arrivals at
R req/s for --duration seconds and waits for completions — latency then
includes queueing behind the instance, the classic open-vs-closed
distinction. --pattern shapes the arrivals: `uniform` spacing, `bursty`
(back-to-back groups of --burst with --intra-gap-ms inside a burst), or
`trickle` (synonym for uniform at a rate whose gap exceeds any batching
window — the worst case for a static window).

--adaptive runs the feedback-window comparison: the bursty and trickle
scenarios each execute twice — static window (--max-delay-ms, the PR 1
behavior) vs adaptive (same initial window, per-key retuning) — and the
occupancy / tail-latency deltas are printed. --smoke is the CI gate: a tiny
closed-loop run that fails loudly when coalescing stops working.

Usage:
    PYTHONPATH=src python benchmarks/load_bench.py --concurrency 8
    PYTHONPATH=src python benchmarks/load_bench.py --concurrency 8 --backend orchestrated
    PYTHONPATH=src python benchmarks/load_bench.py --rate 200 --duration 5 --modes fused-batched
    PYTHONPATH=src python benchmarks/load_bench.py --adaptive
    PYTHONPATH=src python benchmarks/load_bench.py --coldstart
    PYTHONPATH=src python benchmarks/load_bench.py --smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.dispatch import TRACER
from repro.configs import get_arch, reduced_config
from repro.core import FusionPolicy, OrchestratedBackend, TinyJaxBackend
from repro.models.model import build_model
from repro.scheduler import percentiles_ms
from repro.serving.continuous import ContinuousBatcher
from repro.serving.engine import ServingEngine

BACKENDS = {"tinyjax": TinyJaxBackend, "orchestrated": OrchestratedBackend}
MODES = ("unfused-serial", "unfused-batched", "fused-serial", "fused-batched")


def build_engine(args, fused: bool, adaptive: bool = False, kv_pages: int = 0,
                 tracing: bool = True):
    cfg = reduced_config(get_arch(args.arch))
    model = build_model(cfg)
    policy = FusionPolicy(min_observations=2, merge_cost_s=0.0, enabled=fused)
    platform = BACKENDS[args.backend](
        policy, max_batch=args.max_batch or args.concurrency, max_delay_ms=args.max_delay_ms,
        adaptive=adaptive, tracing=tracing,
    )
    engine = ServingEngine(model, platform, max_len=args.max_len,
                           kv_pages=kv_pages, kv_page_size=args.page_size)
    return engine, platform


def warm(engine, steps: int = 6):
    """Trigger observation->fusion (when enabled) and all compiles."""
    tokens = jnp.ones((1, 4), jnp.int32)
    engine.generate({"tokens": tokens}, steps=steps)
    engine.platform.merger.wait_idle()


class Client:
    """One closed-loop stream: prefill once, then decode step after step.

    The next-token choice is elided (a constant token is fed every step):
    token identity changes neither shapes nor decode cost, and per-step
    argmax/host-roundtrip in N GIL-sharing client threads would measure the
    load generator, not the platform under test. Caches and cur_len advance
    normally, so every step is a real full decode."""

    def __init__(self, engine, cid: int, prompt_len: int):
        self.engine = engine
        tokens = jnp.full((1, prompt_len), 1 + cid % 17, jnp.int32)
        _, self.caches, cur_len = engine.prefill({"tokens": tokens})
        # host-side step counter: numpy += 1 is ~1000x cheaper than a JAX
        # dispatch, and N client threads share one GIL
        self.cur_len = np.asarray(cur_len)
        self.tokens = jnp.full((1, 1), 1 + cid % 17, jnp.int32)
        self.latencies: list[float] = []

    def step_serial(self):
        t0 = time.perf_counter()
        _, self.caches = self.engine.decode_step(self.tokens, self.cur_len, self.caches)
        self.latencies.append(time.perf_counter() - t0)
        self.cur_len = self.cur_len + 1

    def step_batched(self):
        t0 = time.perf_counter()
        fut = self.engine.decode_step_async(self.tokens, self.cur_len, self.caches)
        _, self.caches = fut.result()
        self.latencies.append(time.perf_counter() - t0)
        self.cur_len = self.cur_len + 1


def run_closed_loop(args, mode: str, tracing: bool = True) -> dict:
    fused = mode.startswith("fused")
    batched = mode.endswith("batched")
    engine, platform = build_engine(args, fused, tracing=tracing)
    try:
        warm(engine)
        clients = [Client(engine, i, args.prompt_len) for i in range(args.concurrency)]
        # per-mode warmup: compile the batched buckets before the timed window
        barrier = threading.Barrier(args.concurrency)

        def drive(client: Client, steps: int):
            barrier.wait()
            for _ in range(steps):
                client.step_batched() if batched else client.step_serial()

        for phase_steps, timed in ((args.warmup_steps, False), (args.steps, True)):
            for c in clients:
                c.latencies.clear()
            threads = [
                threading.Thread(target=drive, args=(c, phase_steps), daemon=True)
                for c in clients
            ]
            t0 = time.perf_counter()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0
        total = args.steps * args.concurrency
        lats = [l for c in clients for l in c.latencies]
        out = {
            "mode": mode,
            "loop": "closed",
            "requests": total,
            "elapsed_s": round(elapsed, 3),
            "throughput_rps": round(total / elapsed, 2),
            **{k: round(v, 3) for k, v in percentiles_ms(lats).items()},
            "scheduler": platform.scheduler.stats() if batched else None,
        }
        return out
    finally:
        platform.shutdown()


def arrival_offsets(args):
    """Submit offsets (seconds from start) for one open-loop run. `uniform`
    and `trickle` space arrivals at 1/rate; `bursty` fires back-to-back
    groups of --burst (spaced --intra-gap-ms inside the group) with the
    same long-run rate."""
    if args.pattern == "bursty":
        burst = max(1, args.burst)
        interval = burst / args.rate
        gap = args.intra_gap_ms / 1e3
        t = 0.0
        while t < args.duration:
            for j in range(burst):
                yield t + j * gap
            t += interval
    else:
        interval = 1.0 / args.rate
        t = 0.0
        while t < args.duration:
            yield t
            t += interval


def run_open_loop(args, mode: str, adaptive: bool = False) -> dict:
    fused = mode.startswith("fused")
    engine, platform = build_engine(args, fused, adaptive=adaptive)
    try:
        warm(engine)
        clients = [Client(engine, i, args.prompt_len) for i in range(args.concurrency)]
        # warm the batch buckets so open-loop timing excludes compiles, then
        # drop the warmup from the stats and the controllers' learned state —
        # the measured occupancy/tails/windows must reflect measured traffic
        futs = [engine.decode_step_async(c.tokens, c.cur_len, c.caches) for c in clients]
        for f in futs:
            f.result()
        platform.scheduler.reset_stats()
        pending = []
        lats: list[float] = []
        lats_lock = threading.Lock()

        def stamp_completion(t_submit):
            # done-callbacks fire ON completion, so latency includes queueing
            # behind the instance but NOT time spent waiting in a drain loop
            def cb(fut):
                dt = time.perf_counter() - t_submit
                with lats_lock:
                    lats.append(dt)
            return cb

        t0 = time.perf_counter()
        for i, off in enumerate(arrival_offsets(args)):
            now = time.perf_counter()
            if now < t0 + off:
                time.sleep(t0 + off - now)
            c = clients[i % len(clients)]
            # open loop: fire-and-record, do not wait for the response
            fut = engine.decode_step_async(c.tokens, c.cur_len, c.caches)
            fut.add_done_callback(stamp_completion(time.perf_counter()))
            pending.append(fut)
        for fut in pending:
            fut.result()
        elapsed = time.perf_counter() - t0
        # fut.result() returns before that future's done-callbacks are
        # guaranteed to have run — join on the counter so the percentile
        # snapshot isn't short a few tail samples
        join_deadline = time.perf_counter() + 5.0
        while time.perf_counter() < join_deadline:
            with lats_lock:
                if len(lats) >= len(pending):
                    break
            time.sleep(0.001)
        sched = platform.scheduler.stats()
        max_batch = platform.scheduler.max_batch
        return {
            "mode": mode,
            "loop": "open",
            "pattern": args.pattern,
            "window": "adaptive" if adaptive else "static",
            "offered_rps": args.rate,
            "requests": len(pending),
            "elapsed_s": round(elapsed, 3),
            "throughput_rps": round(len(pending) / elapsed, 2),
            **{k: round(v, 3) for k, v in percentiles_ms(lats).items()},
            "mean_batch": round(sched["mean_batch"], 3),
            "occupancy": round(sched["mean_batch"] / max_batch, 3),
            "scheduler": sched,
        }
    finally:
        platform.shutdown()


def run_adaptive_compare(args) -> dict:
    """The feedback-window demonstration: bursty and trickle arrivals, each
    served with the static --max-delay-ms window and with adaptive retuning
    seeded at the same value. The win a single static window cannot have
    both ways: on bursts the adaptive window grows (occupancy up at equal or
    better tails), on trickle it decays to ~0 (no queueing tax on lone
    requests)."""
    import copy

    scenarios = {
        "bursty": dict(pattern="bursty", rate=args.rate, burst=args.burst,
                       intra_gap_ms=args.intra_gap_ms),
        "trickle": dict(pattern="trickle", rate=args.trickle_rate, burst=1,
                        intra_gap_ms=0.0),
    }
    out: dict = {}
    for scen, overrides in scenarios.items():
        for label, adaptive in (("static", False), ("adaptive", True)):
            a = copy.copy(args)
            for k, v in overrides.items():
                setattr(a, k, v)
            res = run_open_loop(a, "fused-batched", adaptive=adaptive)
            out[f"{scen}/{label}"] = res
            print(f"[{scen:>7}/{label:<8}] occupancy {res['occupancy']:.2f} "
                  f"(mean batch {res['mean_batch']:.2f})   p50 {res['p50_ms']:7.1f} ms   "
                  f"p95 {res['p95_ms']:7.1f} ms   ({res['requests']} reqs)")
    b_s, b_a = out["bursty/static"], out["bursty/adaptive"]
    t_s, t_a = out["trickle/static"], out["trickle/adaptive"]
    summary = {
        "bursty_occupancy_static": b_s["occupancy"],
        "bursty_occupancy_adaptive": b_a["occupancy"],
        "bursty_p95_static_ms": b_s["p95_ms"],
        "bursty_p95_adaptive_ms": b_a["p95_ms"],
        "trickle_p50_static_ms": t_s["p50_ms"],
        "trickle_p50_adaptive_ms": t_a["p50_ms"],
        "trickle_added_ms": round(t_a["p50_ms"] - max(t_s["p50_ms"] - args.max_delay_ms, 0.0), 3),
    }
    print(f"\nbursty : occupancy {b_s['occupancy']:.2f} -> {b_a['occupancy']:.2f}   "
          f"p95 {b_s['p95_ms']:.1f} -> {b_a['p95_ms']:.1f} ms")
    print(f"trickle: p50 {t_s['p50_ms']:.1f} -> {t_a['p50_ms']:.1f} ms "
          f"(static window was {args.max_delay_ms:.1f} ms; adaptive decays it to ~0)")
    out["summary"] = summary
    return out


def run_churn(args, *, smoke: bool = False) -> dict:
    """Fission demonstration: a phase-shift workload on the orchestrated
    backend (each execution unit = one pod).

    Phase 1 — a hot synchronous chain H -> L (serial traffic): the platform
    observes the blocking edge and fuses {H, L} into one unit (the merge is
    queued on the reconciler and lands in the post-phase trough).
    Phase 2 — traffic turns concurrent and *direct*: heavy open-loop H
    arrivals oversubscribe the single fused pod while light L arrivals
    starve behind its FIFO. The scheduler's signals (occupancy ~1, queue
    depth) feed FusionPolicy.decide_split, the control plane executes the
    fission epoch, and L's delivered throughput recovers on its own pod.

    Asserts (hard): the merge AND the split both happened, with the regret
    reason recorded; every submitted request resolved (zero dropped or hung
    futures across all epoch transitions). The recovery ratio is printed
    always and enforced only in the full (non-smoke) run.
    """
    from repro.core import FunctionSpec

    duration = 2.5 if smoke else max(4.0, args.duration)
    rate_l = 100.0
    # Two-stage host calibration so the scenario saturates at ANY host
    # speed without outrunning the single-thread submit loop: first size H's
    # compute (fori_loop iteration count — constant compile cost) so one
    # batch-of-4 costs ~80ms on THIS host, then derive H's offered rate from
    # the fused pod's measured capacity (1.4x oversubscription, below).
    wh = jnp.asarray(np.random.RandomState(0).randn(256, 256).astype(np.float32) * 0.05)
    wl = jnp.asarray(np.random.RandomState(1).randn(256, 256).astype(np.float32) * 0.05)
    probe_iters, target_batch_s = 200, 0.080
    probe = jax.jit(
        lambda v: jax.lax.fori_loop(0, probe_iters, lambda i, h: jnp.tanh(h @ wh), v)
    )
    xb = jnp.ones((4, 8, 256), jnp.float32)
    probe(xb).block_until_ready()  # compile
    trials = []
    for _ in range(3):  # best-of-3: contention only ever ADDS time
        t_p = time.perf_counter()
        probe(xb).block_until_ready()
        trials.append(time.perf_counter() - t_p)
    probe_s = max(min(trials), 1e-4)
    heavy_iters = max(100, int(probe_iters * target_batch_s / probe_s))

    # Saturation here is depth-dominant: the oversubscribed pod's queue
    # grows without bound, while mean occupancy blends H's full batches
    # with L's pre-starvation singletons (~0.33 at phase-2 onset) — an
    # occupancy-heavy threshold would make the trigger timing bimodal.
    # min_group_age_s also gives the starvation ~a second to become visible
    # so the measured recovery reflects a real collapse, not an early exit.
    policy = FusionPolicy(
        min_observations=2, merge_cost_s=0.0,
        split_occupancy=0.3, split_depth=10, split_sustain=3,
        min_group_age_s=0.5, remerge_backoff_s=300.0,
    )
    platform = BACKENDS["orchestrated"](
        policy, max_batch=4, max_delay_ms=2.0, adaptive=True,
        fission=True, fission_interval_s=0.1, trough_merges=True, max_defer_s=1.0,
    )

    def fn_h(ctx, params, x):
        h = jax.lax.fori_loop(0, heavy_iters, lambda i, v: jnp.tanh(v @ params), x)
        return ctx.call("L", h)

    def fn_l(ctx, params, x):
        return jnp.tanh(x @ params)

    try:
        platform.deploy(FunctionSpec("H", fn_h, wh))
        platform.deploy(FunctionSpec("L", fn_l, wl))
        x = jnp.ones((8, 256), jnp.float32)

        # --- phase 1: hot sync chain -> fuse (reconciler lands it in the trough)
        for _ in range(4):
            platform.invoke("H", x)
        platform.merger.wait_idle()
        merges = [m for m in platform.merger.merge_log if m.healthy]
        assert merges and set(merges[-1].members) == {"H", "L"}, "phase 1 must fuse {H, L}"

        # warm the fused unit's batch buckets so phase 2 measures traffic, not
        # compiles, then measure one warm batch to size the overload
        for name in ("H", "L"):
            futs = [platform.invoke_async(name, x) for _ in range(4)]
            for f in futs:
                f.result()
        walls = []
        for _ in range(3):  # best-of-3: an overestimated batch cost would
            t_m = time.perf_counter()  # undersize rate_h and never saturate
            futs = [platform.invoke_async("H", x) for _ in range(4)]
            for f in futs:
                f.result()
            walls.append(time.perf_counter() - t_m)
        capacity_rps = 4.0 / max(min(walls), 1e-3)
        # heavy_iters calibration pins capacity near 50 rps, so this stays
        # far below what the submit loop can offer; 300 is a sanity clamp,
        # not a working bound (a binding cap would defeat the saturation)
        rate_h = min(300.0, max(20.0, 1.6 * capacity_rps))
        platform.scheduler.reset_stats()

        # --- phase 2: concurrent direct traffic; H oversubscribes the fused pod
        done: list[tuple[str, float]] = []
        done_lock = threading.Lock()
        failures: list[BaseException] = []

        def stamp(name):
            def cb(fut):
                exc = fut.exception()
                t = time.perf_counter()
                with done_lock:
                    if exc is not None:
                        failures.append(exc)
                    else:
                        done.append((name, t))
            return cb

        pending = []
        t0 = time.perf_counter()
        next_h, next_l = 0.0, 0.0
        # Offer traffic until ~1.5s past the observed split (bounded), so the
        # post-split recovery window always exists — a split landing near the
        # end of a fixed window would leave nothing to measure and flake CI.
        hard_cap = duration + 4.0
        split_seen_at: float | None = None
        while True:
            now = time.perf_counter() - t0
            if split_seen_at is None and any(s.healthy for s in platform.merger.split_log):
                split_seen_at = now
            if now >= hard_cap:
                break
            if split_seen_at is not None and now >= max(duration, split_seen_at + 1.5):
                break
            if now >= next_h:
                fut = platform.invoke_async("H", x)
                fut.add_done_callback(stamp("H"))
                pending.append(fut)
                next_h += 1.0 / rate_h
            if now >= next_l:
                fut = platform.invoke_async("L", x)
                fut.add_done_callback(stamp("L"))
                pending.append(fut)
                next_l += 1.0 / rate_l
            time.sleep(max(0.0, min(next_h, next_l) - (time.perf_counter() - t0)))
        t_submit_end = time.perf_counter()

        hung = 0
        # ONE shared drain budget: a real hang regression must fail fast
        # with the churn diagnostic, not serialize a fresh timeout per
        # stranded future until the CI job itself is killed
        wait_deadline = time.perf_counter() + 120.0
        for fut in pending:
            try:
                fut.result(timeout=max(0.0, wait_deadline - time.perf_counter()))
            except FuturesTimeout:
                hung += 1
            except Exception:
                pass  # already counted via the done-callback
        # done-callbacks fire after result() returns; join on the counter
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            with done_lock:
                if len(done) + len(failures) >= len(pending):
                    break
            time.sleep(0.001)

        splits = [s for s in platform.merger.split_log if s.healthy]
        stats = platform.stats()
        assert splits, "phase 2 must split the saturated fused group"
        split_t = splits[0].t_completed
        assert not failures, f"requests failed across epoch transitions: {failures[:3]}"
        assert hung == 0, f"{hung} requests hung across epoch transitions"

        # L's delivered throughput: starved behind the fused pod's FIFO
        # before the split, back at its offered rate after it
        settle = 0.5  # post-split compile/settling excluded from the rate
        l_pre = [t for (n, t) in done if n == "L" and t0 <= t < split_t]
        l_post = [t for (n, t) in done if n == "L" and split_t + settle <= t <= t_submit_end]
        pre_rate = len(l_pre) / max(split_t - t0, 1e-9)
        post_span = max(t_submit_end - (split_t + settle), 1e-9)
        post_rate = len(l_post) / post_span
        # floor the denominator at 1 req/s: total pre-split starvation
        # (pre_rate 0) is the strongest possible recovery, not a 1e11x ratio
        recovery = post_rate / max(pre_rate, 1.0)
        out = {
            "mode": "churn",
            "requests": len(pending),
            "failed": len(failures),
            "hung": hung,
            "merge_epoch": merges[-1].epoch,
            "split_epoch": splits[0].epoch,
            "split_reason": splits[0].reason,
            "epoch": stats["lifecycle"]["epoch"],
            "l_rate_pre_split": round(pre_rate, 1),
            "l_rate_post_split": round(post_rate, 1),
            "recovery": round(recovery, 2),
        }
        print(f"[churn] merge @epoch {out['merge_epoch']} -> split @epoch {out['split_epoch']} "
              f"({out['split_reason']})")
        print(f"[churn] L throughput {pre_rate:.1f} -> {post_rate:.1f} req/s "
              f"({recovery:.2f}x recovery), {len(pending)} requests, "
              f"0 failed, 0 hung, final epoch {out['epoch']} "
              f"(H offered {rate_h:.0f} rps vs ~{capacity_rps:.0f} rps capacity)")
        assert split_t < t_submit_end, "split must land while traffic is still offered"
        # the smoke floor is loose (shared CI boxes); the full run is a demo
        # and must show a real recovery
        assert recovery >= (1.2 if smoke else 1.3), (
            f"fission must recover the starved member's throughput (got {recovery:.2f}x)"
        )
        return out
    finally:
        platform.shutdown()


def run_coldstart(args, *, smoke: bool = False) -> dict:
    """Restore-not-rebuild gate: warm provisioning must beat cold builds.

    Scenario A — warm churn. One platform fuses a hot H -> L chain, splits
    it, and re-fuses it, ``cycles`` times. Cycle 1 pays the cold compiles;
    every later cycle must be served ENTIRELY from the executable index:
    the dispatch tracer is armed from cycle 2 and asserts zero backend
    compiles, and the warm merges' build time must beat the cold one.

    Scenario B — resurrect-from-zero. A standalone function is deployed
    cold (first invoke pays trace + XLA compile), then parked via
    ``scale_to_zero`` (params snapshotted, routes dropped) and invoked
    again: the resurrect restores the snapshot, hits the executable index,
    and must produce a bit-identical answer with zero compiles, >=Nx
    faster than the cold start.

    Both ratios are enforced: >=3x in smoke (shared 2-core CI boxes),
    >=5x in the full run — the PR's headline claim.
    """
    import tempfile

    from repro.core import FunctionSpec
    from repro.launch.compile_cache import EXECUTABLE_INDEX, disable_persistent_cache

    cycles = 3 if smoke else 5
    floor = 3.0 if smoke else 5.0
    # cold must mean cold: no restore from JAX's persistent cache, whatever
    # JAX_COMPILATION_CACHE_DIR says
    disable_persistent_cache()
    EXECUTABLE_INDEX.clear()

    # --- scenario A: merge -> split -> re-merge churn --------------------
    rs = np.random.RandomState(0)
    wh = jnp.asarray(rs.randn(256, 256).astype(np.float32) * 0.05)
    wl = jnp.asarray(rs.randn(256, 256).astype(np.float32) * 0.05)
    policy = FusionPolicy(min_observations=2, merge_cost_s=0.0,
                          min_group_age_s=0.0, remerge_backoff_s=0.0)
    platform = BACKENDS["tinyjax"](policy)

    def fn_h(ctx, params, x):
        h = jnp.tanh(x @ params)
        return ctx.call("L", h)

    def fn_l(ctx, params, x):
        return jnp.tanh(x @ params)

    armed = False
    try:
        platform.deploy(FunctionSpec("H", fn_h, wh))
        platform.deploy(FunctionSpec("L", fn_l, wl))
        x = jnp.ones((8, 256), jnp.float32)
        base = TRACER.snapshot()
        for cycle in range(cycles):
            for _ in range(4):
                platform.invoke("H", x)
            platform.merger.wait_idle()
            merges = [m for m in platform.merger.merge_log if m.healthy]
            assert len(merges) == cycle + 1, (
                f"cycle {cycle}: expected {cycle + 1} merges, saw {len(merges)}"
            )
            ev = platform.merger.split(
                frozenset({"H", "L"}), [{"H"}, {"L"}], reason="coldstart churn"
            )
            assert ev is not None and ev.healthy, f"cycle {cycle}: split failed"
            if cycle == 0:
                # everything this loop will ever build is now compiled and
                # indexed — from here on, churn must restore, not rebuild
                base = TRACER.snapshot()
                TRACER.arm()
                armed = True
        churn_delta = TRACER.delta(base)
        TRACER.disarm()
        armed = False

        merges = [m for m in platform.merger.merge_log if m.healthy]
        splits = [s for s in platform.merger.split_log if s.healthy]
        assert len(merges) == cycles and len(splits) == cycles
        assert all(m.warm for m in merges[1:]), (
            f"re-merges must be index-served: {[m.warm for m in merges]}"
        )
        assert all(s.warm for s in splits[1:]), (
            f"re-splits must be index-served: {[s.warm for s in splits]}"
        )
        assert churn_delta.compiles == 0, (
            f"steady-state churn recompiled {churn_delta.compiles} programs"
        )
        cold_build = merges[0].build_s
        warm_builds = [m.build_s for m in merges[1:]]
        churn_ratio = cold_build / max(sum(warm_builds) / len(warm_builds), 1e-9)
        cstats = platform.provisioning_stats()["compile_cache"]
    finally:
        if armed:
            TRACER.disarm()
        platform.shutdown()

    # --- scenario B: park (scale-to-zero) -> resurrect -------------------
    snapdir = tempfile.mkdtemp(prefix="coldstart_snap_")
    platform2 = BACKENDS["tinyjax"](
        FusionPolicy(enabled=False), snapshot_dir=snapdir
    )

    def leaf_fn(ctx, params, x):
        h = x
        for w in params["ws"]:  # unrolled: XLA compile cost scales with depth
            h = jnp.tanh(h @ w)
        return h

    rs = np.random.RandomState(7)
    ws = tuple(jnp.asarray(rs.randn(192, 192).astype(np.float32) * 0.05)
               for _ in range(8))
    armed = False
    try:
        platform2.deploy(FunctionSpec("leaf", leaf_fn, {"ws": ws}))
        x2 = jnp.asarray(rs.randn(4, 192).astype(np.float32))
        t0 = time.perf_counter()
        r_cold = np.asarray(platform2.invoke("leaf", x2))
        t_cold = time.perf_counter() - t0

        r_ref = np.asarray(platform2.invoke("leaf", x2))
        assert np.array_equal(r_cold, r_ref)
        parked = platform2.scale_to_zero("leaf")
        assert parked == ("leaf",), f"park failed: {parked!r}"
        assert platform2.provisioning_stats()["parked"] == ["leaf"]

        base = TRACER.snapshot()
        TRACER.arm()
        armed = True
        t0 = time.perf_counter()
        r_warm = np.asarray(platform2.invoke("leaf", x2))
        t_warm = time.perf_counter() - t0
        rez_delta = TRACER.delta(base)
        TRACER.disarm()
        armed = False

        assert rez_delta.compiles == 0, (
            f"resurrect recompiled {rez_delta.compiles} programs"
        )
        assert np.array_equal(r_warm, r_ref), "resurrected output must be bit-identical"
        rez_ratio = t_cold / max(t_warm, 1e-9)
        billing = platform2.meter.summary().get("provisioning", {})
    finally:
        if armed:
            TRACER.disarm()
        platform2.shutdown()

    out = {
        "mode": "coldstart",
        "cycles": cycles,
        "churn_cold_build_s": round(cold_build, 4),
        "churn_warm_build_s": round(sum(warm_builds) / len(warm_builds), 4),
        "churn_ratio": round(churn_ratio, 1),
        "steady_state_compiles": churn_delta.compiles,
        "executable_cache": cstats,
        "resurrect_cold_s": round(t_cold, 4),
        "resurrect_warm_s": round(t_warm, 4),
        "resurrect_ratio": round(rez_ratio, 1),
        "resurrect_compiles": rez_delta.compiles,
        "billing_provisioning": billing,
    }
    print(f"[coldstart] churn: cold build {cold_build * 1e3:.1f} ms, warm "
          f"{out['churn_warm_build_s'] * 1e3:.1f} ms ({churn_ratio:.1f}x), "
          f"{churn_delta.compiles} steady-state compiles over {cycles - 1} warm cycles")
    print(f"[coldstart] resurrect: cold start {t_cold * 1e3:.1f} ms, "
          f"resurrect {t_warm * 1e3:.1f} ms ({rez_ratio:.1f}x), "
          f"{rez_delta.compiles} compiles, bit-identical output")
    assert churn_ratio >= floor, (
        f"warm re-merge must be >={floor}x faster than cold (got {churn_ratio:.1f}x)"
    )
    assert rez_ratio >= floor, (
        f"resurrect must be >={floor}x faster than cold start (got {rez_ratio:.1f}x)"
    )
    return out


def run_coldstart_smoke(args) -> int:
    """CI gate for warm provisioning; one retry (same policy as the other
    smokes — timing ratios can flake on shared boxes, counter assertions
    cannot, and a real regression fails both attempts)."""
    try:
        run_coldstart(args, smoke=True)
        return 0
    except AssertionError:
        print("[coldstart-smoke] attempt 1 flaked; retrying once")
        try:
            run_coldstart(args, smoke=True)
            return 0
        except AssertionError as exc:
            print(f"[coldstart-smoke] FAIL: {exc}")
            return 1


def run_replicas(args, *, smoke: bool = False) -> dict:
    """Replicated-data-plane gate: rho-driven autoscaling must recover the
    throughput a single hot instance caps, on hot-skewed load at fixed
    concurrency.

    The hot function models the I/O-bound FaaS handler replication exists
    for: eager local compute, a fixed host-side wait (the downstream RPC
    most real handlers block on), then a boundary ``ctx.call`` to the
    downstream function — so the entry runs on the platform's eager glue
    path, per request, on its pod's own thread. The wait releases the GIL,
    so replica pods overlap their waits — the speedup is a property of the
    data plane, not of how many cores the CI box happens to have — while
    the single-instance baseline serializes every request through one
    pod's FIFO. (A compiled-program sleep via ``pure_callback`` would NOT
    show this: XLA host callbacks share one runtime thread on small boxes,
    serializing the waits platform-wide.)

    Run A (baseline): one instance, no autoscaler. Run B: the same offered
    load with ``autoscale_config`` — the scheduler's predicted rho crosses
    the threshold, the autoscaler spins replicas out through the warm
    provisioning path, and least-outstanding spread fans the lanes across
    the set. Asserted:

    * autoscaled throughput >= 1.5x the single-instance baseline;
    * the strict class meets the SAME fixed p95 target in BOTH runs
      (replication must not cost conformance);
    * every scale-out provisioning record is warm, and the dispatch tracer
      (armed from the end of run B's warmup) sees ZERO compiles — replica
      spin-up restores from the executable index, never rebuilds;
    * spread picks land on >= 2 replicas (the set actually shares load).
    """
    from repro.core import FunctionSpec
    from repro.scheduler.slo import SLOClass

    from repro.scheduler.adaptive import AdaptiveConfig

    duration = 2.0 if smoke else max(4.0, args.duration)
    ramp = 1.5  # run B: unmeasured window for the autoscaler to act in
    io_wait_s = 0.005  # the simulated downstream RPC — host-independent
    max_batch = 4
    strict = SLOClass("gold", 250.0)
    strict_rate = 10.0

    w = jnp.asarray(np.random.RandomState(0).randn(64, 64).astype(np.float32) * 0.05)

    def fn_hot(ctx, params, x):
        y = jnp.tanh(x @ params)      # eager local compute
        time.sleep(io_wait_s)         # the downstream RPC's network wait
        return ctx.call("downstream", y)  # boundary: keeps the entry eager

    def fn_downstream(ctx, params, x):
        return x + 1.0

    # hot-skewed load: 8 shape-distinct closed-loop BE clients (one lane
    # each — replication is under test here, not coalescing) + a strict
    # trickle on its own shape, all on ONE function
    n_clients = 8
    lane_xs = [jnp.ones((4 + lane, 64), jnp.float32) for lane in range(n_clients)]
    x_strict = jnp.ones((3, 64), jnp.float32)

    def build(autoscale: bool):
        platform = BACKENDS["orchestrated"](
            FusionPolicy(enabled=False), max_batch=max_batch, max_delay_ms=2.0,
            adaptive=True,  # predicted_rho needs the adaptive estimators
            # single-client lanes never fill a batch: an uncapped window
            # would grow toward occupancy and dominate every round trip
            adaptive_config=AdaptiveConfig(max_delay_s=0.002),
            be_shed_depth=10**6,  # measure conservation, not shedding
            autoscale=autoscale,
            autoscale_config=dict(
                rho_high=0.35, rho_low=0.05, sustain=2,
                max_replicas=3, cooldown_s=0.25, eval_interval_s=0.05,
            ) if autoscale else None,
        )
        platform.deploy(FunctionSpec("downstream", fn_downstream, None))
        platform.deploy(FunctionSpec("hot", fn_hot, w))
        # compile (and index) every program the run can touch — one
        # downstream program per lane shape; the hot entry itself is
        # boundary glue (nothing to compile). A mid-run compile after this
        # would trip the spin-up tracer gate.
        for x in (*lane_xs, x_strict):
            platform.invoke("hot", x)
        return platform

    def drive(platform, span_s: float) -> dict:
        """Closed-loop BE clients + open-loop strict trickle for span_s."""
        strict_lats: list[float] = []
        lock = threading.Lock()
        counts = [0] * n_clients
        t_end = time.perf_counter() + span_s

        def be_client(cid: int):
            x = lane_xs[cid % len(lane_xs)]
            while time.perf_counter() < t_end:
                platform.invoke_async("hot", x).result(timeout=120)
                counts[cid] += 1

        def strict_client():
            futs = []
            while time.perf_counter() < t_end:
                t_s = time.perf_counter()
                fut = platform.invoke_async("hot", x_strict, slo=strict)

                def cb(_fut, t_submit=t_s):
                    dt = time.perf_counter() - t_submit
                    with lock:
                        strict_lats.append(dt)
                fut.add_done_callback(cb)
                futs.append(fut)
                time.sleep(1.0 / strict_rate)
            for f in futs:
                f.result(timeout=120)

        threads = [threading.Thread(target=be_client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        threads.append(threading.Thread(target=strict_client, daemon=True))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        with lock:
            lats = list(strict_lats)
        return {
            "requests": sum(counts),
            "elapsed_s": elapsed,
            "throughput_rps": sum(counts) / elapsed,
            "strict_p95_ms": percentiles_ms(lats)["p95_ms"] if lats else 0.0,
            "strict_requests": len(lats),
        }

    # --- run A: single instance, no autoscaler -------------------------
    platform = build(autoscale=False)
    try:
        base = drive(platform, duration)
        assert platform.registry.replica_count("hot") == 1
    finally:
        platform.shutdown()

    # --- run B: same load, rho-driven autoscaling ----------------------
    platform = build(autoscale=True)
    armed = False
    try:
        tr0 = TRACER.snapshot()
        TRACER.arm()  # spin-ups from here on must be restore-not-rebuild
        armed = True
        drive(platform, ramp)  # unmeasured: the autoscaler reacts in here
        n_replicas = platform.registry.replica_count("hot")
        assert n_replicas >= 2, (
            f"autoscaler never scaled out under saturation (replicas={n_replicas})"
        )
        auto = drive(platform, duration)
        spinups = TRACER.delta(tr0)
        TRACER.disarm()
        armed = False

        replicas = platform.stats()["replicas"]
        info = replicas["functions"]["hot"]
        prov = platform.provisioning_stats()
        scale_outs = [e for e in prov["events"] if e["kind"] == "scale-out"]
    finally:
        if armed:
            TRACER.disarm()
        platform.shutdown()

    ratio = auto["throughput_rps"] / max(base["throughput_rps"], 1e-9)
    out = {
        "mode": "replicas",
        "baseline_rps": round(base["throughput_rps"], 1),
        "autoscaled_rps": round(auto["throughput_rps"], 1),
        "speedup": round(ratio, 2),
        "replicas": len(info["replicas"]),
        "picks": info["picks"],
        "spread": replicas["spread"],
        "spinup_estimate_s": replicas["spinup_estimate_s"],
        "scale_outs": len(scale_outs),
        "spinup_compiles": spinups.compiles,
        "strict_target_ms": strict.target_p95_ms,
        "baseline_strict_p95_ms": round(base["strict_p95_ms"], 1),
        "autoscaled_strict_p95_ms": round(auto["strict_p95_ms"], 1),
    }
    print(f"[replicas] single instance: {base['throughput_rps']:8.1f} req/s   "
          f"strict p95 {base['strict_p95_ms']:6.1f} ms   ({base['requests']} reqs)")
    print(f"[replicas] autoscaled x{out['replicas']}: {auto['throughput_rps']:8.1f} req/s   "
          f"strict p95 {auto['strict_p95_ms']:6.1f} ms   ({auto['requests']} reqs)")
    print(f"[replicas] speedup {ratio:.2f}x   {out['scale_outs']} warm scale-outs "
          f"({spinups.compiles} compiles)   picks {out['picks']}")
    assert scale_outs and all(e["warm"] for e in scale_outs), (
        f"replica spin-up must be warm (restore-not-rebuild): {scale_outs}"
    )
    assert spinups.compiles == 0, (
        f"replica spin-ups recompiled {spinups.compiles} programs — the "
        f"executable index is not covering the replicated route"
    )
    busy = [iid for iid, n in info["picks"].items() if n > 0]
    assert len(busy) >= 2, f"spread never fanned out: picks {info['picks']}"
    for label, res in (("baseline", base), ("autoscaled", auto)):
        assert res["strict_p95_ms"] <= strict.target_p95_ms, (
            f"{label} strict p95 {res['strict_p95_ms']:.1f}ms > "
            f"{strict.target_p95_ms:.1f}ms target"
        )
    assert ratio >= 1.5, (
        f"autoscaled replica set must deliver >=1.5x the single-instance "
        f"baseline (got {ratio:.2f}x)"
    )
    return out


def run_replicas_smoke(args) -> int:
    """CI gate for the replicated data plane; one retry (same policy as the
    other smokes — timing ratios can flake on shared boxes, the warm/compile
    counter assertions cannot, and a real regression fails both attempts)."""
    try:
        run_replicas(args, smoke=True)
        return 0
    except AssertionError:
        print("[replicas-smoke] attempt 1 flaked; retrying once")
        try:
            run_replicas(args, smoke=True)
            return 0
        except AssertionError as exc:
            print(f"[replicas-smoke] FAIL: {exc}")
            return 1


def run_slo(args, *, smoke: bool = False) -> dict:
    """Multi-level SLO demonstration: three classes under mixed open-loop
    load on one calibrated function, on the tinyjax backend with adaptive
    (queueing-model) windows.

    Classes: ``strict`` (finite p95 target derived from the measured batch
    service time so the scenario is host-independent), ``standard`` (4x the
    strict target), and best-effort. Arrivals: best-effort comes in bursts
    (the traffic batching exists for), strict/standard trickle uniformly.

    The same arrival schedule then replays against a FIFO baseline — one
    class, static window, no SLO awareness — and the run asserts the two
    headline properties: the strict class MEETS its p95 target under the
    SLO-aware scheduler, and aggregate throughput stays within 15% of the
    FIFO baseline (class isolation must not cost meaningful capacity).
    """
    from repro.core import FunctionSpec
    from repro.scheduler.slo import SLOClass

    duration = 2.0 if smoke else max(4.0, args.duration)
    max_batch = 4

    # --- host calibration: size F so one batch-of-4 costs ~4ms here ---
    w = jnp.asarray(np.random.RandomState(0).randn(128, 128).astype(np.float32) * 0.05)
    probe_iters, target_batch_s = 50, 0.004
    probe = jax.jit(
        lambda v: jax.lax.fori_loop(0, probe_iters, lambda i, h: jnp.tanh(h @ w), v)
    )
    xb = jnp.ones((max_batch, 4, 128), jnp.float32)
    probe(xb).block_until_ready()  # compile
    trials = []
    for _ in range(3):  # best-of-3: contention only ever ADDS time
        t_p = time.perf_counter()
        probe(xb).block_until_ready()
        trials.append(time.perf_counter() - t_p)
    probe_s = max(min(trials), 1e-5)
    fn_iters = max(10, int(probe_iters * target_batch_s / probe_s))

    def fn_f(ctx, params, x):
        return jax.lax.fori_loop(0, fn_iters, lambda i, v: jnp.tanh(v @ params), x)

    def build(slo_aware: bool):
        platform = BACKENDS["tinyjax"](
            FusionPolicy(enabled=False), max_batch=max_batch,
            max_delay_ms=args.max_delay_ms, adaptive=slo_aware,
        )
        platform.deploy(FunctionSpec("F", fn_f, w))
        return platform

    x = jnp.ones((4, 128), jnp.float32)

    def warm(platform):
        """Compile every bucket the run will touch, outside any timing."""
        for k in (1, 2, max_batch):
            futs = [platform.invoke_async("F", x) for _ in range(k)]
            for f in futs:
                f.result()

    def measure_capacity(platform):
        walls = []
        for _ in range(3):
            t_m = time.perf_counter()
            futs = [platform.invoke_async("F", x) for _ in range(max_batch)]
            for f in futs:
                f.result()
            walls.append(time.perf_counter() - t_m)
        return max_batch / max(min(walls), 1e-4)

    def drive(platform, classes: dict[str, SLOClass], rates: dict[str, float]) -> dict:
        """One open-loop run of the mixed schedule. ``classes`` maps stream
        -> SLOClass (the FIFO baseline maps every stream to None);
        ``rates`` is the SHARED arrival schedule — probed once, replayed
        identically for both runs, so the throughput comparison measures
        class isolation and not probe-to-probe calibration noise."""
        warm(platform)
        platform.scheduler.reset_stats()
        pending: list = []
        lat_by_stream: dict[str, list[float]] = {k: [] for k in rates}
        lock = threading.Lock()

        def stamp(stream, t_submit):
            def cb(fut):
                dt = time.perf_counter() - t_submit
                with lock:
                    lat_by_stream[stream].append(dt)
            return cb

        t0 = time.perf_counter()
        next_t = dict.fromkeys(rates, 0.0)
        burst = 4  # best-effort arrives in back-to-back groups
        while True:
            now = time.perf_counter() - t0
            if now >= duration:
                break
            for stream, rate in rates.items():
                if now >= next_t[stream]:
                    n = burst if stream == "be" else 1
                    for _ in range(n):
                        fut = platform.invoke_async("F", x, slo=classes.get(stream))
                        fut.add_done_callback(stamp(stream, time.perf_counter()))
                        pending.append(fut)
                    next_t[stream] += n / rate
            time.sleep(max(0.0, min(next_t.values()) - (time.perf_counter() - t0)))
        for fut in pending:
            fut.result(timeout=60)
        # done-callbacks can trail result(); join on the counters
        deadline = time.perf_counter() + 5.0
        while time.perf_counter() < deadline:
            with lock:
                if sum(len(v) for v in lat_by_stream.values()) >= len(pending):
                    break
            time.sleep(0.001)
        sched = platform.scheduler.stats()
        return {
            "requests": len(pending),
            "throughput_rps": sched["throughput_rps"],
            "mean_batch": round(sched["mean_batch"], 3),
            "per_stream": {
                k: {kk: round(vv, 2) for kk, vv in percentiles_ms(v).items()}
                for k, v in lat_by_stream.items()
            },
            "classes": sched.get("classes", {}),
        }

    # ONE calibration probe sizes both the targets (~10 batch-times for
    # strict: meaningful AND meetable on any host) and the shared arrival
    # schedule replayed against both platforms
    platform = build(slo_aware=True)
    try:
        warm(platform)
        capacity_rps = measure_capacity(platform)
        batch_s = max_batch / capacity_rps
        strict = SLOClass("strict", max(10 * batch_s * 1e3, 40.0))
        standard = SLOClass("standard", 4 * strict.target_p95_ms)
        classes = {"strict": strict, "standard": standard, "be": None}
        total = 0.55 * capacity_rps  # below capacity: targets are meetable
        rates = {"strict": 0.15 * total, "standard": 0.25 * total, "be": 0.60 * total}
        slo_res = drive(platform, classes, rates)
    finally:
        platform.shutdown()

    platform = build(slo_aware=False)
    try:
        fifo_res = drive(platform, dict.fromkeys(classes, None), rates)  # one class, FIFO
    finally:
        platform.shutdown()

    strict_p95 = slo_res["per_stream"]["strict"]["p95_ms"]
    fifo_strict_p95 = fifo_res["per_stream"]["strict"]["p95_ms"]
    ratio = slo_res["throughput_rps"] / max(fifo_res["throughput_rps"], 1e-9)
    out = {
        "mode": "slo",
        "strict_target_ms": strict.target_p95_ms,
        "strict_p95_ms": strict_p95,
        "fifo_strict_p95_ms": fifo_strict_p95,
        "standard_p95_ms": slo_res["per_stream"]["standard"]["p95_ms"],
        "be_p95_ms": slo_res["per_stream"]["be"]["p95_ms"],
        "throughput_rps": slo_res["throughput_rps"],
        "fifo_throughput_rps": fifo_res["throughput_rps"],
        "throughput_vs_fifo": round(ratio, 3),
        "requests": slo_res["requests"],
        "slo": slo_res,
        "fifo": fifo_res,
    }
    for stream in ("strict", "standard", "be"):
        tgt = {"strict": strict.target_p95_ms, "standard": standard.target_p95_ms,
               "be": float("inf")}[stream]
        tgt_s = f"target {tgt:7.1f} ms" if tgt != float("inf") else "best-effort   "
        print(f"[slo] {stream:>8}: p95 {slo_res['per_stream'][stream]['p95_ms']:7.1f} ms "
              f"({tgt_s})   fifo p95 {fifo_res['per_stream'][stream]['p95_ms']:7.1f} ms")
    print(f"[slo] aggregate throughput {slo_res['throughput_rps']:.1f} rps vs "
          f"FIFO {fifo_res['throughput_rps']:.1f} rps ({ratio:.2f}x), "
          f"{slo_res['requests']} reqs, mean batch {slo_res['mean_batch']:.2f} "
          f"(be lanes), capacity ~{capacity_rps:.0f} rps")
    assert strict_p95 <= strict.target_p95_ms, (
        f"strict class missed its target under mixed load: "
        f"p95 {strict_p95:.1f}ms > {strict.target_p95_ms:.1f}ms"
    )
    assert ratio >= 0.85, (
        f"SLO-aware scheduling cost too much aggregate throughput: "
        f"{ratio:.2f}x of FIFO (floor 0.85)"
    )
    return out


def run_slo_smoke(args) -> int:
    """CI gate for the SLO scheduler: tiny mixed-class run; one retry (same
    policy as the churn smoke — shared 2-core CI boxes can flake the
    calibration ~once in ten runs; a real regression fails both)."""
    try:
        run_slo(args, smoke=True)
        return 0
    except AssertionError:
        print("[slo-smoke] attempt 1 flaked; retrying once")
        try:
            run_slo(args, smoke=True)
            return 0
        except AssertionError as exc:
            print(f"[slo-smoke] FAIL: {exc}")
            return 1


def run_serve(args, *, smoke: bool = False) -> dict:
    """Paged continuous-batching serve demo vs the per-client-pytree
    baseline, at EQUAL client count on the same fused chain.

    Baseline: C closed-loop clients, each with its own full ``max_len``
    dense cache pytree, decoding through the scheduler's micro-batched
    dispatch (the PR 1-4 serve path) — every step is a rendezvous: C
    futures, C cache pytrees stacked/split across the batching boundary.

    Paged: the same C as a ContinuousBatcher capacity over one shared KV
    arena. Open-loop arrivals with MIXED prompt and generation lengths join
    the persistent in-flight batch post-prefill and leave at their step
    limit; empty slots are masked. Tokens/s and p95 inter-token latency are
    reported for both, plus per-request arena pages from the billing meter
    (RAM now proportional to tokens held, not clients x max_len)."""
    import queue as queue_mod

    from repro.serving.engine import _greedy_token

    c = min(args.concurrency, 4) if smoke else args.concurrency
    steps = 12 if smoke else max(16, args.steps // 2)
    prompt_lens = (4, 8) if smoke else (4, 8, 16)
    n_requests = 5 * c
    # the SHARED workload: mixed prompt and generation lengths
    gens = [max(6, steps + ((i * 7) % 13) - 6) for i in range(n_requests)]
    prompts = [jnp.full((1, prompt_lens[i % len(prompt_lens)]), 1 + i % 17, jnp.int32)
               for i in range(n_requests)]

    # --- paged continuous batching over the shared arena (calibrates the
    # open-loop arrival schedule both sides then replay)
    width = args.max_len // args.page_size
    kv_pages = (c + 2) * width + 1  # in-flight residents + margin + scratch
    engine, platform = build_engine(args, fused=True, kv_pages=kv_pages)
    try:
        warm(engine)  # fuse the chain + compile the dense routes
        cb = ContinuousBatcher(engine, capacity=c)
        # warmup: compile each prefill length + the capacity-C decode program
        futs = [cb.submit({"tokens": prompts[i]}, 3) for i in range(min(c, len(prompts)))]
        for f in futs:
            f.result(timeout=300)
        # the workload repeats prompts, and a repeated prompt is now a
        # whole-prefix cache hit served by one frozen decode step — compile
        # that program too before anything is timed (the calibration request
        # below is itself such a hit)
        cb.submit({"tokens": prompts[0]}, 3).result(timeout=300)
        # calibrate arrivals so the in-flight batch stays occupied (~1.5x
        # oversubscribed vs the paged solo rate); the identical offsets
        # replay against the baseline, so whichever side is slower simply
        # backs up — open-loop throughput measures capacity
        t_cal = time.perf_counter()
        cb.submit({"tokens": prompts[0]}, steps).result(timeout=300)
        per_req_s = max(time.perf_counter() - t_cal, 1e-3)
        offsets = [i * per_req_s / (1.5 * c) for i in range(n_requests)]
        # warmup + calibration must not pollute the measured leases/occupancy
        platform.meter.reset()
        cb.reset_stats()
        # dispatch-hygiene gate: warmup compiled every program the stream
        # can touch, so the timed window must compile nothing and must not
        # sync the host more than once per batched step (+ seat/finish per
        # request) — a per-token-per-lane sync or a mid-stream recompile
        # shows up here, not in a reviewer's profile later
        TRACER.arm()
        dispatch_t0 = TRACER.snapshot()
        results = []
        t0 = time.perf_counter()
        pend = []
        for i in range(n_requests):
            target = t0 + offsets[i]
            now = time.perf_counter()
            if now < target:
                time.sleep(target - now)
            pend.append(cb.submit({"tokens": prompts[i]}, gens[i]))
        for f in pend:
            results.append(f.result(timeout=600))
        paged_elapsed = time.perf_counter() - t0
        hygiene = TRACER.delta(dispatch_t0)
        TRACER.disarm()
        print(f"[serve] dispatch hygiene: {hygiene.compiles} steady-state compiles, "
              f"{hygiene.host_syncs} host syncs over {hygiene.decode_steps} decode steps "
              f"/ {n_requests} requests")
        assert hygiene.compiles == 0, (
            f"steady-state serve stream compiled {hygiene.compiles} new program(s) "
            f"after warmup — a shape bucket is leaking"
        )
        sync_budget = hygiene.decode_steps + 2 * n_requests + c
        assert hygiene.host_syncs <= sync_budget, (
            f"{hygiene.host_syncs} device->host syncs for {hygiene.decode_steps} "
            f"decode steps (budget {sync_budget}: one batched token fetch per "
            f"step + seat/finish per request) — something syncs per token"
        )
        paged_tokens = sum(r["tokens"].shape[1] for r in results)
        itl = [s for r in results for s in r["step_s"]]
        arena = platform.meter.arena_summary()
        stats = cb.stats()
        cb.shutdown()
        paged = {
            "tokens_s": round(paged_tokens / paged_elapsed, 1),
            "itl_p95_ms": round(percentiles_ms(itl)["p95_ms"], 2),
            "tokens": paged_tokens,
            "elapsed_s": round(paged_elapsed, 3),
            "mean_occupancy": round(stats["mean_occupancy"], 3),
            "mean_pages_per_request": round(arena["mean_pages"], 2),
            "max_pages_per_request": arena["max_pages"],
            "arena_gb_s": arena["gb_s"],
        }
    finally:
        platform.shutdown()

    # --- baseline: the SAME open-loop request stream served by C client
    # workers, each request with its own full max_len dense cache pytree,
    # decode steps through the scheduler's micro-batched dispatch (the
    # pre-arena serve path, at equal client count)
    engine, platform = build_engine(args, fused=True)
    try:
        warm(engine)
        # compile every prefill length and every batched decode bucket the
        # run can touch — the timed stream must measure traffic, not compiles
        for pl in prompt_lens:
            engine.generate({"tokens": jnp.full((1, pl), 2, jnp.int32)}, steps=3)
        warm_clients = [Client(engine, i, prompt_lens[0]) for i in range(c)]
        k = 1
        while k <= c:
            futs = [engine.decode_step_async(cl.tokens, cl.cur_len, cl.caches)
                    for cl in warm_clients[:k]]
            for f in futs:
                f.result()
            k *= 2
        platform.scheduler.reset_stats()
        work: "queue_mod.Queue" = queue_mod.Queue()
        base_lats: list[float] = []
        base_tokens_done = [0]
        lock = threading.Lock()

        def serve_one(prompt, gen):
            logits, caches, cur_len = engine.prefill({"tokens": prompt})
            toks = 1
            tokens = _greedy_token(jnp.asarray(logits))
            lats = []
            for _ in range(gen - 1):
                t_s = time.perf_counter()
                logits, caches = engine.decode_step_async(tokens, cur_len, caches).result()
                lats.append(time.perf_counter() - t_s)
                cur_len = cur_len + 1
                tokens = _greedy_token(jnp.asarray(logits))
                toks += 1
            with lock:
                base_lats.extend(lats)
                base_tokens_done[0] += toks

        def worker():
            while True:
                item = work.get()
                if item is None:
                    return
                target_t, prompt, gen = item
                now = time.perf_counter()
                if now < target_t:
                    time.sleep(target_t - now)
                serve_one(prompt, gen)

        threads = [threading.Thread(target=worker, daemon=True) for _ in range(c)]
        t0 = time.perf_counter()
        for i in range(n_requests):
            work.put((t0 + offsets[i], prompts[i], gens[i]))
        for _ in threads:
            work.put(None)
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        base_elapsed = time.perf_counter() - t0
        base = {
            "tokens_s": round(base_tokens_done[0] / base_elapsed, 1),
            "itl_p95_ms": round(percentiles_ms(base_lats)["p95_ms"], 2),
            "tokens": base_tokens_done[0],
            "elapsed_s": round(base_elapsed, 3),
        }
    finally:
        platform.shutdown()

    ratio = paged["tokens_s"] / max(base["tokens_s"], 1e-9)
    out = {"mode": "serve", "clients": c, "requests": n_requests,
           "baseline": base, "paged": paged, "speedup": round(ratio, 2)}
    print(f"[serve] per-client baseline: {base['tokens_s']:8.1f} tok/s   "
          f"itl p95 {base['itl_p95_ms']:7.2f} ms   ({base['tokens']} tokens)")
    print(f"[serve] paged continuous  : {paged['tokens_s']:8.1f} tok/s   "
          f"itl p95 {paged['itl_p95_ms']:7.2f} ms   ({paged['tokens']} tokens, "
          f"occupancy {paged['mean_occupancy']:.2f})")
    print(f"[serve] speedup {ratio:.2f}x   arena: {paged['mean_pages_per_request']:.1f} mean / "
          f"{paged['max_pages_per_request']} max pages per request "
          f"(vs {args.max_len // args.page_size} pages for a dense max_len cache)")
    # the smoke floor is loose (a 2-core shared box adds +-30% run-to-run
    # noise and the batcher's single loop thread absorbs it all); the full
    # run is the demo and must show the real >= 1.5x effect
    floor = 1.15 if smoke else 1.5
    assert ratio >= floor, (
        f"paged continuous batching must beat the per-client baseline "
        f"(got {ratio:.2f}x, floor {floor}x)"
    )
    out["shared_prefix"] = run_shared_prefix(args, smoke=smoke)
    return out


def run_shared_prefix(args, *, smoke: bool = False) -> dict:
    """Shared-system-prompt scenario: the prefix-cache + chunked-prefill
    story on one engine, two phases over the same burst workload.

    Phase 1 (baseline): ``serialize_prefill=True`` and every request gets a
    DISTINCT 80-token prompt — no page sharing, every admission runs its
    whole prompt in front of the batch (the pre-chunking serve path).
    Phase 2: the default chunked batcher and an IDENTICAL 80-token prompt
    for every request — the fleet-wide system prompt. After the first
    request commits, every joiner's prompt is a whole-prefix cache hit:
    its first token comes from one frozen (no-KV-write) decode step and it
    seats without computing a single prompt token.

    Two deltas are measured and asserted:
    * billed pages/request (ArenaLease amortized by refcount at release):
      sharers split the prefix pages' bill, so the mean must drop >= 2x
      vs the unshared nominal count.
    * joiner stall p95 — per request, the WORST inter-emission gap, i.e.
      what a seated resident absorbed while someone else's prompt ran.
      Serialized 80-token prefills stall every resident; cache hits don't.
    """
    c = min(args.concurrency, 4) if smoke else args.concurrency
    n = 6 * c
    sys_len = 80  # 5 full pages at the default 16-token page
    gens = [6 + (i % 5) for i in range(n)]
    width = args.max_len // args.page_size
    kv_pages = (c + 2) * width + 1
    engine, platform = build_engine(args, fused=True, kv_pages=kv_pages)
    try:
        warm(engine)

        def distinct_prompt(i):
            row = np.full((1, sys_len), 2, np.int32)
            row[0, 0] = 1 + i % 16      # two varied positions: distinct
            row[0, 1] = 1 + (i // 16) % 16  # prompts for any n < 256
            return jnp.asarray(row)

        shared_prompt = jnp.full((1, sys_len), 3, jnp.int32)

        def drive(cb, prompts):
            """Burst-submit the workload and collect per-request results."""
            pend = [cb.submit({"tokens": prompts[i]}, gens[i]) for i in range(n)]
            return [f.result(timeout=600) for f in pend]

        def stall_p95_ms(results):
            worst = [max(r["step_s"]) if r["step_s"] else 0.0 for r in results]
            return percentiles_ms(worst)["p95_ms"]

        # --- phase 1: serialized prefill, no sharing possible
        cb = ContinuousBatcher(engine, capacity=c, serialize_prefill=True)
        for f in [cb.submit({"tokens": distinct_prompt(200 + k)}, 3) for k in range(2)]:
            f.result(timeout=300)  # compile prefill-80 + the decode program
        platform.meter.reset()
        cb.reset_stats()
        res_u = drive(cb, [distinct_prompt(i) for i in range(n)])
        arena_u = platform.meter.arena_summary()
        unshared = {
            "mean_billed_pages": round(arena_u["mean_billed_pages"], 2),
            "mean_pages": round(arena_u["mean_pages"], 2),
            "stall_p95_ms": round(stall_p95_ms(res_u), 2),
        }
        cb.shutdown()

        # --- phase 2: chunked prefill + the shared system prompt
        cb = ContinuousBatcher(engine, capacity=c, prefill_chunk=16)
        for f in [cb.submit({"tokens": shared_prompt}, 3) for _ in range(2)]:
            f.result(timeout=300)  # compile the chunk + frozen-hit programs
        platform.meter.reset()
        cb.reset_stats()
        hits0 = engine.arena.stats()["shared_hits"]
        res_s = drive(cb, [shared_prompt] * n)
        arena_s = platform.meter.arena_summary()
        hits = engine.arena.stats()["shared_hits"] - hits0
        shared = {
            "mean_billed_pages": round(arena_s["mean_billed_pages"], 2),
            "mean_pages": round(arena_s["mean_pages"], 2),
            "stall_p95_ms": round(stall_p95_ms(res_s), 2),
            "shared_hits": hits,
        }
        cb.shutdown()
        engine.arena.check_consistency()
        assert engine.arena.used_pages() == 0, "requests leaked arena pages"
    finally:
        platform.shutdown()

    pages_ratio = unshared["mean_billed_pages"] / max(shared["mean_billed_pages"], 1e-9)
    stall_ratio = unshared["stall_p95_ms"] / max(shared["stall_p95_ms"], 1e-9)
    out = {
        "mode": "shared-prefix", "clients": c, "requests": n,
        "unshared": unshared, "shared": shared,
        "pages_ratio": round(pages_ratio, 2), "stall_ratio": round(stall_ratio, 2),
    }
    print(f"[serve] shared-prefix: billed pages/request "
          f"{unshared['mean_billed_pages']:.2f} -> {shared['mean_billed_pages']:.2f} "
          f"({pages_ratio:.2f}x lower; {hits}/{n} prefix hits)")
    print(f"[serve] joiner stall p95: {unshared['stall_p95_ms']:8.2f} ms serialized/unshared"
          f" -> {shared['stall_p95_ms']:8.2f} ms chunked/shared ({stall_ratio:.2f}x)")
    assert hits >= n - 1, f"shared prompts must hit the prefix cache ({hits}/{n})"
    assert pages_ratio >= 2.0, (
        f"prefix sharing must cut billed pages/request >= 2x "
        f"(got {pages_ratio:.2f}x)"
    )
    # the stall floor is loose in smoke (shared 2-core boxes): a cache hit
    # skips the whole prompt, so the real effect is several-fold
    stall_floor = 1.2 if smoke else 1.5
    assert stall_ratio >= stall_floor, (
        f"cache hits must shrink the joiner stall tail "
        f"(got {stall_ratio:.2f}x, floor {stall_floor}x)"
    )
    return out


def run_serve_smoke(args) -> int:
    """CI gate for the paged serve path; one retry (same policy as the other
    smokes on shared 2-core CI boxes)."""
    try:
        run_serve(args, smoke=True)
        return 0
    except AssertionError:
        print("[serve-smoke] attempt 1 flaked; retrying once")
        try:
            run_serve(args, smoke=True)
            return 0
        except AssertionError as exc:
            print(f"[serve-smoke] FAIL: {exc}")
            return 1


def run_smoke(args) -> int:
    """CI gate: a few seconds of closed-loop traffic on the tiny model. Fails
    (exit 1) when coalescing stops happening or throughput collapses to
    zero — scheduler regressions then fail the workflow, not just tests."""
    args.concurrency = min(args.concurrency, 4)
    args.steps, args.warmup_steps = 10, 3
    args.prompt_len, args.max_len = 4, 48
    res = run_closed_loop(args, "fused-batched")
    sched = res["scheduler"] or {}
    print(f"[smoke] {res['throughput_rps']:.1f} req/s, p95 {res['p95_ms']:.1f} ms, "
          f"mean batch {sched.get('mean_batch', 0):.2f} over {sched.get('batches', 0)} batches")
    ok = res["throughput_rps"] > 0 and sched.get("mean_batch", 0.0) > 1.05
    if not ok:
        print("[smoke] FAIL: scheduler no longer coalesces concurrent traffic")
    # tracing-overhead gate: the recorder is always on in production
    # configs, so its cost on the SAME closed-loop traffic must stay under
    # 3% throughput. One retry: on a shared 2-core box run-to-run noise
    # alone can exceed the margin; a real regression fails both attempts.
    off = run_closed_loop(args, "fused-batched", tracing=False)
    ratio = res["throughput_rps"] / max(off["throughput_rps"], 1e-9)
    if ratio < 0.97:
        print(f"[smoke] tracing overhead attempt 1 flaked (on/off ratio {ratio:.3f}); retrying once")
        on2 = run_closed_loop(args, "fused-batched")
        off2 = run_closed_loop(args, "fused-batched", tracing=False)
        ratio = on2["throughput_rps"] / max(off2["throughput_rps"], 1e-9)
    print(f"[smoke] tracing overhead: on/off throughput ratio {ratio:.3f}")
    if ratio < 0.97:
        print("[smoke] FAIL: tracing costs more than 3% throughput")
        ok = False
    # churn gate: merge -> saturate -> split under load, no dropped/hung
    # futures. One retry, same policy as the slow-marked timing tests: on a
    # 2-core shared box the saturation trigger can flake (~10%) on probe
    # noise; a real regression fails both attempts.
    try:
        run_churn(args, smoke=True)
    except AssertionError:
        print("[smoke] churn attempt 1 flaked; retrying once")
        try:
            run_churn(args, smoke=True)
        except AssertionError as exc:
            print(f"[smoke] FAIL (churn): {exc}")
            ok = False
    return 0 if ok else 1


def main():
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--backend", default="tinyjax", choices=sorted(BACKENDS))
    ap.add_argument("--concurrency", type=int, default=8, help="closed-loop clients / open-loop streams")
    ap.add_argument("--steps", type=int, default=48, help="timed decode steps per closed-loop client")
    ap.add_argument("--warmup-steps", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=96)
    ap.add_argument("--max-batch", type=int, default=0, help="0 = match --concurrency")
    ap.add_argument("--max-delay-ms", type=float, default=4.0, help="micro-batch window")
    ap.add_argument("--rate", type=float, default=0.0, help=">0 switches to open loop at this req/s")
    ap.add_argument("--duration", type=float, default=5.0, help="open-loop run time (s)")
    ap.add_argument("--pattern", default="uniform", choices=("uniform", "bursty", "trickle"),
                    help="open-loop arrival pattern")
    ap.add_argument("--burst", type=int, default=8, help="bursty: arrivals per burst")
    ap.add_argument("--intra-gap-ms", type=float, default=1.0, help="bursty: spacing inside a burst")
    ap.add_argument("--trickle-rate", type=float, default=15.0,
                    help="--adaptive: req/s of the trickle scenario (gap must exceed any window)")
    ap.add_argument("--adaptive", action="store_true",
                    help="run the static-vs-adaptive window comparison on bursty + trickle arrivals")
    ap.add_argument("--smoke", action="store_true", help="tiny CI sanity run (exit 1 on regression)")
    ap.add_argument("--churn", action="store_true",
                    help="fission demo: merge -> saturate -> split under load (orchestrated)")
    ap.add_argument("--slo", action="store_true",
                    help="multi-class SLO demo: strict/standard/best-effort under mixed "
                         "load vs a FIFO baseline (with --smoke: tiny CI gate)")
    ap.add_argument("--serve", action="store_true",
                    help="paged continuous-batching serve demo vs the per-client-pytree "
                         "baseline (with --smoke: tiny CI gate)")
    ap.add_argument("--replicas", action="store_true",
                    help="replicated-data-plane demo: rho-driven autoscaling vs the "
                         "single-instance baseline on hot-skewed load "
                         "(with --smoke: tiny CI gate)")
    ap.add_argument("--coldstart", action="store_true",
                    help="warm-provisioning demo: merge/split churn from the executable "
                         "index + scale-to-zero resurrect vs cold build "
                         "(with --smoke: tiny CI gate)")
    ap.add_argument("--page-size", type=int, default=16, help="KV arena page size (tokens)")
    ap.add_argument("--modes", nargs="*", default=["fused-serial", "fused-batched"], choices=MODES)
    ap.add_argument("--json", action="store_true", help="emit machine-readable results")
    ap.add_argument("--trace", metavar="PATH", default=None,
                    help="export a Chrome/perfetto trace_event JSON of every "
                         "platform's request + control-plane spans to PATH at exit")
    args = ap.parse_args()

    if not args.coldstart:
        # persistent XLA cache across runs; the coldstart scenario turns it
        # off itself (run_coldstart)
        from repro.launch.compile_cache import enable_persistent_cache
        enable_persistent_cache()

    if args.trace:
        # pin every tracer created from here on: scenarios drop their
        # platforms, but the spans must survive until the export below
        from repro.obs import retain_tracers
        retain_tracers()
    try:
        _dispatch(args)
    finally:
        if args.trace:
            from repro.obs import export_all_chrome
            export_all_chrome(args.trace)
            print(f"[trace] wrote {args.trace}")


def _dispatch(args):
    if args.coldstart:
        if args.smoke:
            sys.exit(run_coldstart_smoke(args))
        out = run_coldstart(args)
        if args.json:
            print(json.dumps(out, indent=2))
        return
    if args.replicas:
        if args.smoke:
            sys.exit(run_replicas_smoke(args))
        out = run_replicas(args)
        if args.json:
            print(json.dumps(out, indent=2))
        return
    if args.serve:
        if args.smoke:
            sys.exit(run_serve_smoke(args))
        out = run_serve(args)
        if args.json:
            print(json.dumps(out, indent=2))
        return
    if args.slo:
        if args.smoke:
            sys.exit(run_slo_smoke(args))
        out = run_slo(args)
        if args.json:
            out.pop("slo", None)
            out.pop("fifo", None)
            print(json.dumps(out, indent=2))
        return
    if args.smoke:
        sys.exit(run_smoke(args))
    if args.churn:
        out = run_churn(args)
        if args.json:
            print(json.dumps(out, indent=2))
        return
    if args.adaptive:
        if args.rate <= 0:
            # bursts of --burst whose span outlives the static window: the
            # static window fragments each burst into several executions,
            # the adaptive one grows to pack it whole — and because each
            # burst drains before the next, the adaptive wait is bounded by
            # the burst span, never by queueing behind a knife-edge load
            args.rate = 160.0
        out = run_adaptive_compare(args)
        if args.json:
            for r in out.values():
                if isinstance(r, dict):
                    r.pop("scheduler", None)
            print(json.dumps(out, indent=2))
        return

    results = []
    for mode in args.modes:
        if args.rate > 0:
            if mode.endswith("serial"):
                # open loop submits without waiting — that IS the scheduled
                # path; a "serial" open-loop row would silently measure the
                # same thing under a different label
                print(f"[{mode:>16}] skipped: open loop (--rate) only supports *-batched modes")
                continue
            res = run_open_loop(args, mode)
        else:
            res = run_closed_loop(args, mode)
        results.append(res)
        if not args.json:
            sched = res.pop("scheduler", None)
            print(f"[{res['mode']:>16}] {res['throughput_rps']:8.1f} req/s   "
                  f"p50 {res['p50_ms']:7.1f} ms   p95 {res['p95_ms']:7.1f} ms   "
                  f"p99 {res['p99_ms']:7.1f} ms   ({res['requests']} reqs in {res['elapsed_s']}s)")
            if sched:
                print(f"{'':18}mean batch {sched['mean_batch']:.2f}, max {sched['max_batch_seen']}, "
                      f"{sched['batches']} batches")

    by_mode = {r["mode"]: r for r in results}
    if "fused-serial" in by_mode and "fused-batched" in by_mode:
        speedup = by_mode["fused-batched"]["throughput_rps"] / max(by_mode["fused-serial"]["throughput_rps"], 1e-9)
        if args.json:
            for r in results:
                r.pop("scheduler", None)
            print(json.dumps({"results": results, "batched_vs_serial_speedup": round(speedup, 2)}, indent=2))
        else:
            print(f"\nbatched vs serial (fused chain): {speedup:.2f}x throughput")
    elif args.json:
        print(json.dumps({"results": results}, indent=2))


if __name__ == "__main__":
    main()
