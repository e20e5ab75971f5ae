"""Per-step device readings from a reduced trace (``run.reduce_trace``):
each traced engine call (a decode step or a prefill chunk) owns the device
operations that ran between its start and the next call's. The work each
call needed comes from the configuration's counts module (``ctx["counts"]``),
by the name of its function: a decode call's function takes the lengths it
served, a prefill call's the chunk's start and rows."""
from __future__ import annotations

import trace_reduce as tr


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def _steps(ctx, kind: str):
    """(device, call index, what the call served, its ops) of one kind."""
    t = ctx["trace"]
    for dev in t["devices"]:
        for i, ops in dev["steps"].items():
            k = t["marks"][i][1]
            what, data = t["calls"].get(k, (None, None))
            if what == kind:
                yield dev, data, ops


def _count(ctx, kind: str, count: str, data):
    """The counts module's function ``count`` on what one call served."""
    fn = getattr(ctx["counts"], count)
    return fn(ctx["model"], data) if kind == "decode" else fn(ctx["model"], *data)


def step_mfu(ctx, kind: str, count: str):
    """Model FLOPs of the traced calls of ``kind`` (the counts function
    ``count``) over the union of their ops' device time times the bf16
    peak, in %; None without such calls."""
    peaks = ctx["peaks"]
    work, busy = 0.0, 0
    for _, data, ops in _steps(ctx, kind):
        if kind == "decode" and not data:
            continue
        work += _count(ctx, kind, count, data)
        busy += tr.union_ns(ops, min(o.start_ns for o in ops), max(o.end_ns for o in ops))
    if not busy or not work:
        return None
    return 100.0 * work / (busy / 1e9 * peaks["bf16_flops_per_s"])


def kernel_roofline(ctx, kind: str, patterns: tuple[str, ...], count: str):
    """The least time the chip needs for the kernel's work in the traced
    calls of ``kind`` over the kernel's device time, in %; None when no op
    carries the kernel's name. The counts function ``count`` gives one
    (flops, bytes) pair for each layer the call ran the kernel in, and each
    layer's least time is summed."""
    peaks = ctx["peaks"]
    need, spent = 0.0, 0
    for _, data, ops in _steps(ctx, kind):
        kops = [o for o in ops if tr.matches(o, patterns)]
        if not kops or (kind == "decode" and not data):
            continue
        need += sum(roofline_seconds(f, b, peaks) for f, b in _count(ctx, kind, count, data))
        spent += sum(o.dur_ns for o in kops)
    if not spent:
        return None
    return 100.0 * need / (spent / 1e9)
