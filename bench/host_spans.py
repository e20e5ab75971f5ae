#!/usr/bin/env python3
"""Where the device's idle time fell on the host, from the program's spans.

The program marks its host phases as profiler annotations on the batcher's
loop thread (``serve.*``, with ``engine.*`` and ``platform.*`` inside them)
and its merge health checks as control-timeline spans (``merge.check``
around ``merge.replay_*`` and ``merge.compare``). This module splits the
traced window's idle gaps (``trace_reduce.idle_gaps``) by the innermost
program span that covers each part of them, and sums the health checks.

    python3 bench/host_spans.py --workload <cell> --seed <n> --seconds <s>

runs the cell once with ``--trace 1`` through ``run.py`` and prints, after
the result line, one JSON line: the idle split by layer and by span; the
ten longest gaps, each with its split, the engine call it ends in and the
garbage collection inside it; the garbage collections and the
``serve.decode_step`` times in the window; ``setup_s`` and the merge health
checks' split by phase before the window; and what one annotation costs on
this host with the profiler off and on.
"""
from __future__ import annotations

import bisect
import gc
import json
import os
import statistics
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import trace_reduce as tr  # noqa: E402

#: the program's span names start with these; the first names the layer
PROGRAM = ("serve.", "engine.", "platform.", "obs.")
#: layer -> the span prefixes that belong to it
LAYERS = {"batcher": ("serve.", "obs."), "dispatch": ("engine.", "platform.")}


def program_spans(host: list[tuple[str, tr.Op]]) -> list[tr.Op]:
    """The program's spans on the host line that carries ``serve.`` spans
    (the batcher's loop thread), by start."""
    lines = {line for line, ev in host if ev.name.startswith("serve.")}
    spans = [ev for line, ev in host if line in lines and ev.name.startswith(PROGRAM)]
    return sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns))


def innermost(spans: list[tr.Op]) -> list[tuple[int, int, str]]:
    """(start, end, name) pieces of time, each under one innermost span,
    from spans that nest as on one thread's stack."""
    points = []
    for i, s in enumerate(spans):
        points.append((s.start_ns, 1, -s.dur_ns, i))
        points.append((s.end_ns, 0, 0, i))
    points.sort()
    stack: list[int] = []
    out, last = [], None
    for t, is_start, _, i in points:
        if stack and t > last:
            out.append((last, t, spans[stack[-1]].name))
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
        last = t
    return out


def split_gaps(gaps: list[tuple[int, int]], pieces: list[tuple[int, int, str]]) -> dict:
    """ns of the gaps under each innermost span's name; ``None`` holds the
    part under no program span."""
    out: dict = {}
    j = 0
    for s, e in sorted(gaps):
        while j < len(pieces) and pieces[j][1] <= s:
            j += 1
        covered = 0
        k = j
        while k < len(pieces) and pieces[k][0] < e:
            ov = min(e, pieces[k][1]) - max(s, pieces[k][0])
            if ov > 0:
                out[pieces[k][2]] = out.get(pieces[k][2], 0) + ov
                covered += ov
            k += 1
        if e - s > covered:
            out[None] = out.get(None, 0) + (e - s - covered)
    return out


def layer_of(name: str | None) -> str | None:
    return next((layer for layer, prefixes in LAYERS.items()
                 if name is not None and name.startswith(prefixes)), None)


def idle_split(ops: list[tr.Op], host: list[tuple[str, tr.Op]], lo: int, hi: int) -> dict:
    """Shares (%) of the window [lo, hi) that are idle under each layer's
    innermost spans (``batcher_idle_share``, ``dispatch_idle_share``) and
    under none, beside ``device_idle_share``; ns by span name."""
    window = hi - lo
    by_name = split_gaps(tr.idle_gaps(ops, lo, hi), innermost(program_spans(host)))
    layers: dict = {}
    for name, ns in by_name.items():
        layers[layer_of(name)] = layers.get(layer_of(name), 0) + ns
    return {
        "device_idle_share": 100.0 * (1.0 - tr.union_ns(ops, lo, hi) / window),
        "batcher_idle_share": 100.0 * layers.get("batcher", 0) / window,
        "dispatch_idle_share": 100.0 * layers.get("dispatch", 0) / window,
        "unattributed_idle_share": 100.0 * layers.get(None, 0) / window,
        "idle_s_by_span": {str(k): v / 1e9 for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])},
    }


def merge_checks(records) -> dict:
    """Seconds of the control-timeline spans of the health checks, by
    phase name, and how many checks there were."""
    out: dict = {"checks": 0}
    for r in records:
        if r.name.startswith("merge.") and r.ph == "X":
            out[r.name] = out.get(r.name, 0.0) + (r.t1 - r.t0)
            out["checks"] += r.name == "merge.check"
    return out


# ----------------------------------------------------------- the chip run


def annotation_cost_us(n: int = 100_000) -> float:
    """Microseconds one entered and exited ``TraceAnnotation`` takes."""
    import jax

    t = time.perf_counter()
    for _ in range(n):
        with jax.profiler.TraceAnnotation("cost.probe", i=1):
            pass
    return (time.perf_counter() - t) / n * 1e6


def main(argv=None) -> int:
    # jax and the program are imported only once run.main has set up the
    # compilation cache and the import path
    import run

    argv = list(sys.argv[1:] if argv is None else argv)
    kept: dict = {}
    reduce_trace, run_cell = run.reduce_trace, run.run_cell
    # the interpreter's garbage collections, on the tracer's clock
    # (perf_counter): a long one stalls whichever thread triggered it
    pauses: list[tuple[float, float]] = []
    started = [0.0]

    def on_gc(phase: str, info: dict) -> None:
        if phase == "start":
            started[0] = time.perf_counter()
        else:
            pauses.append((started[0], time.perf_counter()))

    gc.callbacks.append(on_gc)

    def reduce_and_split(log_dir: str, calls: dict) -> dict:
        from repro.obs import anchor_offset_ns

        reduced = reduce_trace(log_dir, calls)
        path = tr.find_xplane(log_dir)
        trace = tr.load(path)
        win = next(ev for _, ev in trace.host_events if ev.name == "bench.trace_window")
        lo, hi = win.start_ns, win.end_ns
        ops = trace.device_ops[sorted(trace.device_ops)[0]]
        kept["idle"] = idle_split(ops, trace.host_events, lo, hi)
        pieces = innermost(program_spans(trace.host_events))
        offset = anchor_offset_ns(path)
        gc_ns = [] if offset is None else [(a * 1e9 + offset, b * 1e9 + offset) for a, b in pauses]
        starts = [m[0] for m in reduced["marks"]]
        kept["longest_gaps"] = []
        for s, e in tr.idle_gaps(ops, lo, hi)[:10]:
            i = bisect.bisect_right(starts, e) - 1  # the engine call the gap ends in
            kept["longest_gaps"].append({
                "s": (e - s) / 1e9,
                "call": calls.get(reduced["marks"][i][1], (None,))[0] if i >= 0 else None,
                "gc_s": sum(max(0.0, min(e, b) - max(s, a)) for a, b in gc_ns) / 1e9,
                "by_span": {str(k): v / 1e9 for k, v in split_gaps([(s, e)], pieces).items()},
            })
        in_window = sorted((b - a for a, b in gc_ns if lo <= a < hi), reverse=True)
        kept["gc_in_window"] = {"n": len(in_window), "s": sum(in_window) / 1e9,
                                "longest_s": in_window[0] / 1e9 if in_window else None}
        steps = [ev.dur_ns / 1e6 for _, ev in trace.host_events
                 if ev.name == "serve.decode_step" and lo <= ev.start_ns < hi]
        kept["decode_step_ms"] = {"n": len(steps), "median": statistics.median(steps) if steps else None}
        kept["anchor_offset_ns"] = offset
        return reduced

    def keep_control_spans(system) -> None:
        from repro.obs import CONTROL_TRACE_ID

        rec = system.platform.tracer.recorder
        clear = rec.clear

        def clear_after_keeping() -> None:
            if "merge_checks" not in kept:  # the first clear opens the window
                kept["setup_s"] = time.perf_counter() - run.T_PROCESS
                kept["merge_checks"] = merge_checks(
                    [r for r in rec.snapshot() if r.trace_id == CONTROL_TRACE_ID])
            clear()

        rec.clear = clear_after_keeping

    def traced_cell(*args, **kw):
        return run_cell(*args, on_system=keep_control_spans, **kw)

    run.reduce_trace, run.run_cell = reduce_and_split, traced_cell
    rc = run.main(argv + ["--trace", "1"])
    if rc:
        return rc
    import tempfile

    import jax

    kept["annotation_us_profiler_off"] = annotation_cost_us()
    jax.profiler.start_trace(tempfile.mkdtemp())
    try:
        kept["annotation_us_profiler_on"] = annotation_cost_us()
    finally:
        jax.profiler.stop_trace()
    print(json.dumps(kept), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
