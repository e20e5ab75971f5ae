#!/usr/bin/env python3
"""Run one benchmark cell once on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in ``BENCHMARK.json``) names a model
configuration (``bench/configs/<config>.json``) and a traffic mix
(``bench/traffic/<traffic>.json``); per-layer metrics are readers in
``bench/metrics/<name>.py``. The configuration file names, by their paths
from the checkout's root, its plain reference (``"reference"``: a module
with ``served_gaps``, as ``bench/reference.py``) and the module that counts
its work (``"counts"``: the functions ``bench/flops.py`` has, which the
readers reach as ``ctx["counts"]``). A new cell, mix, metric or model
family is a new file; this script does not change.

One run, in one process:

1. set-up: the model is built through the normal path (``build_model`` ->
   ``ServingEngine`` on ``TinyJaxBackend`` with the default fusion policy
   -> paged KV arena -> ``ContinuousBatcher``), weights drawn on the
   device from the seed; warm-up rounds serve one short request per padded
   prefill size of the mix until a round compiles nothing and leaves the
   chain's instances and merges as they were (a chain that does not
   settle fails the run, with no result); then a lead-in of the mix
   itself brings the batch to its steady occupancy;
2. the window: requests of the mix arrive open loop, each due at its
   Poisson arrival time and timed from it; every request due in the window
   counts, also when it finishes in the bounded drain after it;
3. ``memory_peak_bytes`` is read, the program's state is freed, and a
   seeded sample of the finished requests, the longest among them, is
   checked against the configuration's float32 reference.

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` traces a
few seconds of the window with the profiler and prints the per-layer
metrics. The last line of standard output is the JSON result; the numbers
compared for ``correct`` come last in it and on standard error. Without a
TPU, or with fewer chips than the cell asks for, it prints no result and
exits 2.

``--sweep r1,r2,...`` (not a cell) serves the mix at each offered rate in
turn on one engine and prints one line of readings per rate, to find the
highest rate the cell sustains. ``--control 1`` puts the fp8 control of the
configuration's reference (see ``bench/reference.py``) in the program's
place on the same sample: its gaps are the numbers compared, so a sound
control run reads ``correct`` false, and the program's own gaps are printed
beside them. The benchmark's own runs never do.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import loadgen  # noqa: E402

#: where the run keeps JAX's persistent compilation cache and its traces:
#: fixed paths inside the checkout (listed in .gitignore)
CACHE_DIR = os.path.join(ROOT, ".bench_cache", "jax")
TRACE_DIR = os.path.join(ROOT, ".bench_cache", "trace")
#: warm-up rounds before the harness gives up waiting for the chain to
#: settle (a settled chain takes three)
MAX_WARM_ROUNDS = 4
#: the traced part of the window: its start and length as shares of it
TRACE_AT, TRACE_SHARE, TRACE_MAX_S = 0.3, 0.4, 6.0
#: the correctness sample: at least this many served tokens and requests,
#: and at most this many requests
CHECK_TOKENS, CHECK_MIN_REQUESTS, CHECK_MAX_REQUESTS = 300, 3, 8
#: a cell offers this share of the highest rate its sweep sustained
CELL_SHARE = 0.8


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T_PROCESS:8.2f}s] {msg}", file=sys.stderr, flush=True)


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(benchmark, cell, configuration, mix) for a cell name; the
    configuration's reference and counts modules are loaded here, so a
    configuration that names either wrongly is refused before the run."""
    bench = read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = read_json(os.path.join(ROOT, cfg_entry["file"]))
    mix = read_json(os.path.join(HERE, "traffic", f"{cell['traffic']}.json"))
    model_modules(config)
    return bench, cell, config, mix


def weight_seed(seed: int) -> int:
    """A 31-bit seed for the weights that depends on every bit of ``seed``
    (the PRNG key the program makes from it keeps 32 bits)."""
    import numpy as np

    return int(np.random.SeedSequence(seed).generate_state(1)[0] & 0x7FFFFFFF)


def p95(values: list[float]) -> float:
    """Nearest-rank 95th percentile."""
    v = sorted(values)
    return v[max(0, math.ceil(0.95 * len(v)) - 1)]


def peaks_for(kind: str) -> dict:
    """The chip's peaks from ``bench/peaks.json``; an unknown kind is an
    error, never a default."""
    table = read_json(os.path.join(HERE, "peaks.json"))["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in bench/peaks.json")
    return table[kind]


@functools.cache
def load_module(path: str):
    """The Python file at ``path`` (from the checkout's root), loaded once
    per process."""
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in path)
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return load_module(f"bench/metrics/{name}.py")


def model_modules(config: dict) -> tuple:
    """(reference, counts): the modules the configuration file names under
    those keys. A configuration that leaves either out is refused: no
    reference or counts are assumed for it."""
    for key in ("reference", "counts"):
        if key not in config:
            raise ValueError(f"configuration {config.get('name')!r} names no {key!r} module "
                             f"(a path from the checkout's root)")
    return load_module(config["reference"]), load_module(config["counts"])


# --------------------------------------------------------------- the system


class Served:
    """What the harness sees of one request: when it was due and submitted
    (host clock) and what the batcher returned."""

    __slots__ = ("req", "due", "sent", "done", "tokens", "step_s", "queued_s", "error")

    def __init__(self, req, due: float):
        self.req, self.due = req, due
        self.sent = self.done = None
        self.tokens = self.step_s = self.queued_s = self.error = None

    @property
    def ok(self) -> bool:
        return (self.error is None and self.tokens is not None
                and len(self.tokens) == self.req.max_new_tokens)

    def emissions(self) -> list[float]:
        """Host-clock instants of each token: the first at admission, then
        one per decode step."""
        t = self.sent + self.queued_s
        out = [t]
        for gap in self.step_s:
            t += gap
            out.append(t)
        return out


class System:
    """The system under test, built through its normal serving path."""

    def __init__(self, config: dict, seed: int):
        import dataclasses

        import jax

        from repro.configs.base import ModelConfig
        from repro.core import TinyJaxBackend
        from repro.models.model import build_model
        from repro.serving.continuous import ContinuousBatcher
        from repro.serving.engine import ServingEngine

        self.model = dict(config["model"])
        cfg = ModelConfig(name=config["name"], **self.model)
        self.cfg = dataclasses.replace(cfg, source=config["source"])
        serving = config["serving"]
        self.platform = TinyJaxBackend()
        self.engine = ServingEngine(build_model(self.cfg), self.platform,
                                    max_len=serving["max_len"], seed=weight_seed(seed),
                                    kv_pages=serving["kv_pages"],
                                    kv_page_size=serving["kv_page_size"])
        jax.block_until_ready([self.platform.spec_of(n).params for n in self.engine.chain_names()])
        self.batcher = ContinuousBatcher(self.engine, capacity=serving["capacity"])

    def instances(self) -> int:
        return len(self.platform.registry.live_instances())

    def merges(self) -> int:
        return len(self.platform.merger.merge_log)

    def merge_log(self) -> list[str]:
        """Each merge the platform attempted: its members, and why it was
        refused where it was."""
        return [f"{'+'.join(e.members)}: " + ("ok" if e.healthy else f"refused ({e.reason}: {e.detail})")
                for e in self.platform.merger.merge_log]

    def submit(self, s: Served) -> None:
        import jax.numpy as jnp

        s.sent = time.perf_counter()
        try:
            fut = self.batcher.submit({"tokens": jnp.asarray(s.req.prompt[None, :])},
                                      s.req.max_new_tokens)
        except Exception as exc:  # noqa: BLE001 — a refused request is a failure
            s.error, s.done = repr(exc), time.perf_counter()
            return

        def done(f):
            s.done = time.perf_counter()
            exc = f.exception()
            if exc is not None:
                s.error = repr(exc)
                return
            r = f.result()
            s.tokens = [int(t) for t in r["tokens"][0]]
            s.step_s = list(r["step_s"])
            s.queued_s = float(r["queued_s"])

        fut.add_done_callback(done)

    def shutdown(self, timeout: float) -> bool:
        """Stop the batcher and the platform; False when the batcher still
        held requests."""
        self.batcher.shutdown(timeout=timeout)
        st = self.batcher.stats()
        self.platform.shutdown()
        return st["active"] == 0 and not st["prefilling"] and not any(st["queued"].values())


def wait_all(served: list[Served], deadline: float, system: "System") -> None:
    """Wait for every request until the deadline, or until the batcher's
    loop thread has died (then nothing more will finish)."""
    while time.perf_counter() < deadline and any(s.done is None for s in served):
        if not system.batcher._thread.is_alive():
            log("the batcher's loop thread has ended; its requests will not finish")
            return
        time.sleep(0.05)


def warm_up(system: System, mix: dict, seed: int, vocab: int, page: int) -> dict:
    """Serve one short request per padded prefill size, round after round,
    until a round compiles nothing and leaves instances and merges as they
    were. Returns what the rounds saw."""
    from repro.analysis.dispatch import TRACER

    rounds = []
    for k in range(MAX_WARM_ROUNDS):
        snap, inst0, merges0 = TRACER.snapshot(), system.instances(), system.merges()
        reqs = loadgen.warmup_requests(mix, seed + k, vocab, page)
        served = [Served(r, time.perf_counter()) for r in reqs]
        for s in served:
            system.submit(s)
        t = time.perf_counter()
        wait_all(served, t + 600, system)
        d = TRACER.delta(snap)
        rounds.append({"wall_s": round(time.perf_counter() - t, 3),
                       "compiles": d.compiles, "compile_s": round(d.compile_s, 3),
                       "instances": system.instances(), "merges": system.merges()})
        log(f"warm-up round {k}: {rounds[-1]}")
        if any(not s.ok for s in served):
            raise RuntimeError(f"warm-up request failed: {[s.error for s in served if not s.ok]}")
        if d.compiles == 0 and system.instances() == inst0 and system.merges() == merges0:
            return {"rounds": rounds, "settled": True}
    return {"rounds": rounds, "settled": False}


# -------------------------------------------------------------- the window


class Recorder:
    """Engine calls seen while the profiler traces: each call is wrapped in
    a numbered annotation, and what it served (lengths) is kept under the
    same number."""

    def __init__(self, engine):
        import jax

        self.on = False
        self.seq = 0
        self.calls: dict[int, tuple] = {}
        decode, chunk = engine.paged_decode_step, engine.prefill_chunk_paged

        def paged_decode_step(tokens, cur_len, block_table, *, write_kv=True):
            if not self.on:
                return decode(tokens, cur_len, block_table, write_kv=write_kv)
            k, self.seq = self.seq, self.seq + 1
            lengths = [int(c) + 1 for c in cur_len if int(c) > 0]
            self.calls[k] = ("decode", lengths)
            with jax.profiler.TraceAnnotation(f"bench.call#{k}"):
                return decode(tokens, cur_len, block_table, write_kv=write_kv)

        def prefill_chunk_paged(job, max_tokens):
            if not self.on:
                return chunk(job, max_tokens)
            k, self.seq = self.seq, self.seq + 1
            rows = max(1, min(int(max_tokens), job.t_in - job.pos))
            self.calls[k] = ("prefill", (int(job.pos), rows))
            with jax.profiler.TraceAnnotation(f"bench.call#{k}"):
                return chunk(job, max_tokens)

        engine.paged_decode_step = paged_decode_step
        engine.prefill_chunk_paged = prefill_chunk_paged


def drive(system: System, reqs: list, window_start: float, seconds: float, drain_s: float,
          *, on_open=None, on_close=None, trace=None) -> tuple[list[Served], dict]:
    """Submit ``reqs`` open loop from now; returns every request's record
    and the window's host-clock bounds."""
    s0 = time.perf_counter()
    t0, t1 = s0 + window_start, s0 + window_start + seconds
    served = [Served(r, s0 + r.due_s) for r in reqs]
    opened = False
    tracer_thread = None
    for s in served:
        delay = s.due - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if s.req.in_window and not opened:
            opened = True
            if on_open:
                on_open()
            if trace is not None:
                tracer_thread = threading.Thread(target=trace, args=(t0, seconds), daemon=True)
                tracer_thread.start()
        system.submit(s)
    delay = t1 - time.perf_counter()
    if delay > 0:
        time.sleep(delay)
    if on_close:
        on_close()
    wait_all(served, t1 + drain_s, system)
    drained = time.perf_counter()
    if tracer_thread is not None:
        tracer_thread.join()
    return served, {"t0": t0, "t1": t1, "s0": s0, "drained": drained}


def end_to_end(served: list[Served], win: dict) -> dict:
    """The tails over every request due in the window: one that was served
    (also when a full arena cut its reply short) from its own readings, one
    that got no first token at the time it failed or the drain ended, so
    a failure lengthens the tail and never leaves it."""
    t0, t1 = win["t0"], win["t1"]
    window = [s for s in served if s.req.in_window]
    got = [s for s in window if s.tokens is not None]
    ttft = [(s.sent - s.due) + s.queued_s for s in got]
    ttft += [(s.done if s.done is not None else win["drained"]) - s.due
             for s in window if s.tokens is None]
    itl = [g for s in got for g in s.step_s]
    emitted = sum(1 for s in served if s.tokens is not None
                  for t in s.emissions() if t0 <= t <= t1)
    return {
        "ttft_p95_ms": 1e3 * p95(ttft) if ttft else None,
        "itl_p95_ms": 1e3 * p95(itl) if itl else None,
        "tokens_per_s": emitted / (t1 - t0),
    }


def lateness(served: list[Served]) -> tuple[float, float]:
    late = [s.sent - s.due for s in served if s.req.in_window and s.sent is not None]
    return (max(late), sum(late) / len(late)) if late else (0.0, 0.0)


# ------------------------------------------------------------- correctness


def pick_sample(served: list[Served], seed: int) -> list[Served]:
    """The longest finished window request, then others drawn from the
    seed until the sample holds ``CHECK_TOKENS`` served tokens and
    ``CHECK_MIN_REQUESTS`` requests."""
    import numpy as np

    done = [s for s in served if s.req.in_window and s.ok]
    if not done:
        return []
    longest = max(done, key=lambda s: (len(s.tokens), len(s.req.prompt)))
    rest = [s for s in done if s is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    sample, n = [longest], len(longest.tokens)
    for i in order:
        if (n >= CHECK_TOKENS and len(sample) >= CHECK_MIN_REQUESTS) \
                or len(sample) >= CHECK_MAX_REQUESTS:
            break
        sample.append(rest[i])
        n += len(rest[i].tokens)
    return sample


def check(reference, model: dict, seed: int, sample: list[Served], limits: dict,
          control: bool) -> dict:
    """The gaps of the sample's served tokens below the best logits of the
    configuration's ``reference`` module, each compared with its limit."""
    import numpy as np

    seqs = [(s.req.prompt, np.asarray(s.tokens, np.int32)) for s in sample]
    t = time.perf_counter()
    got = reference.served_gaps(model, weight_seed(seed), seqs, control=control, log=log)
    out = {"requests": len(seqs), "tokens": int(sum(len(x[1]) for x in seqs)),
           "reference_s": round(time.perf_counter() - t, 3)}

    def gaps(arrays) -> dict:
        return {"max_logit_gap": float(max(float(np.max(g)) for g in arrays)),
                "mean_logit_gap": float(np.mean(np.concatenate(arrays)))}

    if control:
        # the control's tokens stand where the program's were: its gaps are
        # compared, the program's are reported beside them
        out.update({f"program_{k}": v for k, v in gaps([g[0] for g in got]).items()})
        out.update(gaps([g[1] for g in got]))
    else:
        out.update(gaps(got))
    out["checks"] = {name: {"value": out[name], "limit": limit} for name, limit in limits.items()}
    return out


# ------------------------------------------------------------------- trace


def trace_window(rec: Recorder, log_dir: str):
    import jax

    def run(t0: float, seconds: float) -> None:
        length = min(TRACE_MAX_S, TRACE_SHARE * seconds)
        time.sleep(max(0.0, t0 + TRACE_AT * seconds - time.perf_counter()))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # Python tracing would slow the host loop under test
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.trace_window"):
                rec.on = True
                time.sleep(length)
                rec.on = False
        finally:
            jax.profiler.stop_trace()

    return run


def reduce_trace(log_dir: str, calls: dict) -> dict:
    import trace_reduce as tr

    trace = tr.load(tr.find_xplane(log_dir))
    win = [ev for _, ev in trace.host_events if ev.name == "bench.trace_window"]
    if not win or not trace.device_ops:
        raise RuntimeError(f"trace holds no window or no device ops: planes {trace.lines}")
    lo, hi = win[0].start_ns, win[0].end_ns
    marks = sorted((ev.start_ns, int(ev.name.split("#")[1])) for _, ev in trace.host_events
                   if ev.name.startswith("bench.call#"))
    starts = [m[0] for m in marks]
    devices = []
    for plane, ops in sorted(trace.device_ops.items()):
        owner = tr.assign(ops, starts)
        steps: dict[int, list] = {}
        for op, i in zip(ops, owner):
            if 0 <= i < len(marks) - 1:  # the last call may run past the window
                steps.setdefault(i, []).append(op)
        devices.append({"plane": plane, "ops": ops, "steps": steps,
                        "busy_ns": tr.union_ns(ops, lo, hi)})
    first = devices[0]
    all_ops = first["ops"]
    gaps = tr.idle_gaps(all_ops, lo, hi)
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_ns"] for d in devices) / len(devices) / 1e9,
        "marks": marks,
        "calls": calls,
        "devices": devices,
        "breakdown": {
            "device_ops": tr.top_ops(all_ops, lo, hi),
            "idle_gaps": tr.name_gaps(gaps, trace.host_events, thread_of="bench.call#"),
        },
    }


# -------------------------------------------------------------------- main


def device_info(jax) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())}


def peak_bytes(jax) -> int | None:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in jax.devices()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def bytes_in_use(jax) -> int | None:
    used = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()]
    used = [u for u in used if u is not None]
    return max(used) if used else None


def run_cell(bench: dict, cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, control: bool = False, on_system=None) -> dict:
    """One run of a cell in this process; returns the result line's dict.
    The caller has checked the device."""
    import jax

    from repro.analysis.dispatch import TRACER
    from repro.launch.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    reference, counts = model_modules(config)
    model = dict(config["model"])
    page = config["serving"]["kv_page_size"]
    system = System(config, seed)
    if on_system is not None:
        on_system(system)
    log(f"built {config['name']}: {system.instances()} instances, "
        f"{time.perf_counter() - T_PROCESS:.1f} s since start, "
        f"device memory in use {bytes_in_use(jax)} B")
    TRACER.arm()
    warm = warm_up(system, mix, seed, model["vocab_size"], page)
    settled = system.instances()
    log(f"settled: {settled} chain instances, {system.merges()} merges, "
        f"settled={warm['settled']}, device memory peak {peak_bytes(jax)} B, "
        f"in use {bytes_in_use(jax)} B; merges {system.merge_log()}")
    warm["merges"] = system.merge_log()
    if not warm["settled"]:
        # an unfused edge runs its caller as eager glue that compiles on
        # every call: the window would serve next to nothing
        system.shutdown(timeout=30)
        raise RuntimeError(f"the chain did not settle in {MAX_WARM_ROUNDS} warm-up rounds "
                           f"({settled} instances; merges {warm['merges']})")
    if not trace:
        TRACER.disarm()

    reqs, window_start = loadgen.schedule(mix, seed, seconds, model["vocab_size"])
    marks: dict = {}
    rec = Recorder(system.engine) if trace else None
    log_dir = os.path.join(TRACE_DIR, f"{cell['name']}-{seed}")
    if trace:
        shutil.rmtree(log_dir, ignore_errors=True)

    def opened():
        marks["setup_s"] = time.perf_counter() - T_PROCESS
        marks["open"] = (TRACER.snapshot(), system.instances(), system.merges())
        system.platform.tracer.recorder.clear()

    def closed():
        marks["close"] = (TRACER.snapshot(), system.instances(), system.merges())

    served, win = drive(system, reqs, window_start, seconds, mix["drain_s"],
                        on_open=opened, on_close=closed,
                        trace=trace_window(rec, log_dir) if trace else None)
    in_window = [s for s in served if s.req.in_window]
    failed = [s for s in in_window if not s.ok]
    late_max, late_mean = lateness(served)
    log(f"window: {len(in_window)} requests due, {len(failed)} failed, generator late "
        f"max {late_max * 1e3:.3f} ms mean {late_mean * 1e3:.3f} ms")
    for s in failed[:5]:
        log(f"  failed request {s.req.index}: error={s.error} tokens="
            f"{None if s.tokens is None else len(s.tokens)}/{s.req.max_new_tokens}")
    peak = peak_bytes(jax)
    spans = [r for r in system.platform.tracer.recorder.snapshot()
             if win["t0"] <= r.t0 <= win["t1"]]
    counters = {"open": marks["open"], "close": marks["close"]}
    TRACER.disarm()
    calls = rec.calls if rec is not None else {}
    rec = None  # its wrappers hold the engine
    clean = system.shutdown(timeout=120)
    del system
    gc.collect()
    log(f"device memory in use once the program is freed: {bytes_in_use(jax)} B")
    if not clean:
        raise RuntimeError("the batcher did not stop within its drain")

    result: dict = {"attempted": len(in_window), "failed": len(failed)}
    if trace:
        peaks = peaks_for(device_info(jax)["kind"])
        reduced = reduce_trace(log_dir, calls)
        shutil.rmtree(log_dir, ignore_errors=True)
        ctx = {"spans": spans, "counters": counters, "trace": reduced, "model": model,
               "counts": counts, "peaks": peaks, "window": win}
        metrics = {}
        for m in bench["per_layer"]:
            value = load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = reduced["breakdown"]
        extra = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
    else:
        e2e = end_to_end(served, win)
        e2e["peak_hbm_gb"] = peak / 1e9 if peak is not None else None
        e2e["setup_s"] = marks["setup_s"]
        metrics = {}
        for m in bench["end_to_end"]:
            if e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
        result["metrics"] = metrics
        extra = {}
    result["device"] = dict(device_info(jax), memory_peak_bytes=peak, **extra)

    sample = pick_sample(served, seed)
    del served
    readings = {}
    if sample:
        verdict = check(reference, model, seed, sample, config["limits"], control)
        log(f"reference: {verdict['requests']} requests, {verdict['tokens']} served tokens, "
            f"{verdict['reference_s']} s")
        if control:
            log(f"control in the program's place; the program's own max logit gap "
                f"{verdict['program_max_logit_gap']}, mean {verdict['program_mean_logit_gap']}")
            for k in ("program_max_logit_gap", "program_mean_logit_gap"):
                readings[k] = verdict[k]
        readings["mean_logit_gap"] = verdict["mean_logit_gap"]
        readings["reference_s"] = verdict["reference_s"]
        checks = verdict["checks"]
        correct = all(c["value"] <= c["limit"] for c in checks.values())
    else:
        checks, correct = {"finished_requests": {"value": 0, "limit": 1}}, False
    result = {"correct": correct, **result, "settled_instances": settled,
              "warm_up": warm, **readings, "checks": checks}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def sweep(bench: dict, cell: dict, config: dict, mix: dict, *, seed: int, seconds: float,
          rates: list[float]) -> list[dict]:
    """Serve the mix at each rate in turn on one engine; one reading each."""
    from repro.launch.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    model = config["model"]
    system = System(config, seed)
    warm = warm_up(system, mix, seed, model["vocab_size"], config["serving"]["kv_page_size"])
    log(f"settled: {system.instances()} chain instances ({warm['settled']}); "
        f"merges {system.merge_log()}")
    out = []
    try:
        for k, rate in enumerate(rates):
            m = dict(mix, rate_per_s=rate)
            reqs, ws = loadgen.schedule(m, seed + k, seconds, model["vocab_size"])
            queued = {}
            served, win = drive(system, reqs, ws, seconds, mix["drain_s"],
                                on_close=lambda: queued.update(system.batcher.stats()))
            in_window = [s for s in served if s.req.in_window]
            e2e = end_to_end(served, win)
            done = [s.done for s in in_window if s.done is not None]
            row = {"rate_per_s": rate, "attempted": len(in_window),
                   "failed": sum(1 for s in in_window if not s.ok),
                   "queued_at_close": sum(queued.get("queued", {}).values()),
                   "active_at_close": queued.get("active"),
                   "drain_s": (max(done) - win["t1"]) if done else None,
                   "late_max_ms": 1e3 * lateness(served)[0], **e2e}
            out.append(row)
            print(json.dumps(row), flush=True)
            if not sustained(row):
                break  # a backlog now only grows at higher rates
    finally:
        system.shutdown(timeout=120)
    held = [r["rate_per_s"] for r in out if sustained(r)]
    if held:
        print(json.dumps({"knee_rate_per_s": max(held),
                          "cell_rate_per_s": round(CELL_SHARE * max(held), 3)}), flush=True)
    return out


def sustained(row: dict) -> bool:
    """No request failed, and less than a second of arrivals still queued
    when the window closed: the backlog did not grow."""
    return row["failed"] == 0 and row["queued_at_close"] <= row["rate_per_s"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default="", help="comma-separated offered rates (req/s)")
    args = ap.parse_args(argv)

    # set before anything imports JAX, which reads them once (the
    # configuration's reference imports it); no size cap: evicting warm-up
    # programs would make set-up compile more with every run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    bench, cell, config, mix = load_cell(args.workload)
    import jax

    info = device_info(jax)
    if info["platform"] != "tpu" or info["count"] < cell["chips"]:
        log(f"bench: needs {cell['chips']} TPU chip(s); JAX sees {info['count']} "
            f"{info['platform']} device(s)")
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    log(f"device: {info}")
    if args.sweep:
        sweep(bench, cell, config, mix, seed=args.seed, seconds=args.seconds,
              rates=[float(r) for r in args.sweep.split(",")])
        return 0
    result = run_cell(bench, cell, config, mix, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), control=bool(args.control))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
