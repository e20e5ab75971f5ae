"""Per-step device readings from a reduced trace (``run.reduce_trace``):
each traced engine call (a decode step or a prefill chunk) owns the device
operations that ran between its start and the next call's."""
from __future__ import annotations

import flops
import trace_reduce as tr


def _steps(ctx, kind: str):
    """(device, call index, what the call served, its ops) of one kind."""
    t = ctx["trace"]
    for dev in t["devices"]:
        for i, ops in dev["steps"].items():
            k = t["marks"][i][1]
            what, data = t["calls"].get(k, (None, None))
            if what == kind:
                yield dev, data, ops


def step_mfu(ctx, kind: str):
    """Model FLOPs of the traced calls of ``kind`` over the union of their
    ops' device time times the bf16 peak, in %; None without such calls."""
    m, peaks = ctx["model"], ctx["peaks"]
    work, busy = 0.0, 0
    for _, data, ops in _steps(ctx, kind):
        if kind == "decode":
            if not data:
                continue
            work += flops.decode_step_flops(m, data)
        else:
            work += flops.prefill_chunk_flops(m, *data)
        busy += tr.union_ns(ops, min(o.start_ns for o in ops), max(o.end_ns for o in ops))
    if not busy or not work:
        return None
    return 100.0 * work / (busy / 1e9 * peaks["bf16_flops_per_s"])


def kernel_roofline(ctx, kind: str, patterns: tuple[str, ...]):
    """The least time the chip needs for the kernel's work in the traced
    calls of ``kind`` (every layer) over the kernel's device time, in %;
    None when no op carries the kernel's name."""
    m, peaks = ctx["model"], ctx["peaks"]
    need, spent = 0.0, 0
    for _, data, ops in _steps(ctx, kind):
        kops = [o for o in ops if tr.matches(o, patterns)]
        if not kops:
            continue
        if kind == "decode":
            if not data:
                continue
            f, b = flops.paged_decode_kernel(m, data)
        else:
            f, b = flops.paged_chunk_kernel(m, *data)
        need += m["num_layers"] * flops.roofline_seconds(f, b, peaks)
        spent += sum(o.dur_ns for o in kops)
    if not spent:
        return None
    return 100.0 * need / (spent / 1e9)
