"""From a profiler trace to device busy time, kernel time and idle gaps.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes with nothing
but JAX. The reduction below it is plain arithmetic on (start, duration)
pairs in nanoseconds, so ``bench/tests/test_trace_reduce.py`` checks it on
small recorded and hand-made traces:

* busy time is the union of the intervals in which an operation ran on a
  device, clipped to the traced window; idle is the rest;
* every device operation belongs to the latest host mark (a benchmark
  annotation around an engine call) that started before it: the batcher
  loop waits for each step's result before it makes the next call, so a
  step's device work lies between its mark and the next one;
* a kernel's time is the summed duration of the operations whose HLO
  name carries its wrapper's name (``paged_decode_attention.3``).
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os


@dataclasses.dataclass(frozen=True)
class Op:
    name: str
    start_ns: int
    dur_ns: int

    @property
    def end_ns(self) -> int:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    device_ops: dict[str, list[Op]]      # device plane -> its ops, by start
    host_events: list[tuple[str, Op]]    # (plane/line index: line name, event)
    lines: dict[str, list[str]]          # plane -> line names (for the record)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load(path: str, op_line: str = "XLA Ops") -> Trace:
    """Device ops from the ``op_line`` line of every ``/device:`` plane, and
    every event of the host planes."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device_ops: dict[str, list[Op]] = {}
    host: list[tuple[str, Op]] = []
    lines: dict[str, list[str]] = {}
    for plane in pd.planes:
        names = []
        for idx, line in enumerate(plane.lines):
            names.append(line.name)
            if plane.name.startswith("/device:"):
                if line.name != op_line:
                    continue
                ops = device_ops.setdefault(plane.name, [])
                for ev in line.events:
                    # an XLA op's event name is its whole HLO instruction;
                    # keep the instruction's name (``%fusion.3``)
                    ops.append(Op(ev.name.split(" = ")[0], int(ev.start_ns), int(ev.duration_ns)))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    host.append((f"{plane.name}/{idx}: {line.name}",
                                 Op(ev.name, int(ev.start_ns), int(ev.duration_ns))))
        lines[plane.name] = names
    for ops in device_ops.values():
        ops.sort(key=lambda o: o.start_ns)
    return Trace({k: v for k, v in device_ops.items() if v}, host, lines)


def union_ns(ops: list[Op], lo: int, hi: int) -> int:
    """Length of the union of the ops' intervals inside [lo, hi)."""
    total, cur_s, cur_e = 0, None, None
    for o in sorted(ops, key=lambda o: o.start_ns):
        s, e = max(o.start_ns, lo), min(o.end_ns, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(ops: list[Op], lo: int, hi: int) -> list[tuple[int, int]]:
    """The intervals of [lo, hi) in which no op ran, longest first."""
    gaps, cursor = [], lo
    for o in sorted(ops, key=lambda o: o.start_ns):
        if o.end_ns <= cursor:
            continue
        if o.start_ns > cursor:
            gaps.append((cursor, min(o.start_ns, hi)))
        cursor = max(cursor, o.end_ns)
        if cursor >= hi:
            break
    if cursor < hi:
        gaps.append((cursor, hi))
    gaps = [(s, e) for s, e in gaps if e > s]
    return sorted(gaps, key=lambda g: g[0] - g[1])


def assign(ops: list[Op], mark_starts: list[int]) -> list[int]:
    """For each op, the index of the latest mark that started at or before
    it, or -1 when it precedes every mark. ``mark_starts`` is sorted."""
    return [bisect.bisect_right(mark_starts, o.start_ns) - 1 for o in ops]


def matches(op: Op, patterns: tuple[str, ...]) -> bool:
    """The op is one of a kernel's calls: its HLO name carries the name of
    the jitted wrapper that issues the ``pallas_call``."""
    return any(p in op.name for p in patterns)


def top_ops(ops: list[Op], lo: int, hi: int, n: int = 10) -> list[list]:
    """The ops that took the most device time in [lo, hi), by name."""
    total: dict[str, int] = {}
    for o in ops:
        if lo <= o.start_ns < hi:
            total[o.name] = total.get(o.name, 0) + o.dur_ns
    best = sorted(total.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in best]


def name_gaps(gaps: list[tuple[int, int]], host: list[tuple[str, Op]], n: int = 10,
              thread_of: str = "") -> list[list]:
    """The ``n`` longest gaps, each named by what the host thread that
    carries events named ``thread_of...`` was doing: the event of that
    thread that overlaps the gap most (the shorter on a tie), its number
    suffix dropped; ``no host event`` when none overlaps."""
    lines = {line for line, ev in host if ev.name.startswith(thread_of)}
    events = [ev for line, ev in host if line in lines]
    out = []
    for s, e in gaps[:n]:
        best, key = "no host event", (0, 0)
        for ev in events:
            ov = min(e, ev.end_ns) - max(s, ev.start_ns)
            if ov > 0 and (ov, -ev.dur_ns) > key:
                best, key = ev.name.split("#")[0], (ov, -ev.dur_ns)
        out.append([best, (e - s) / 1e9])
    return out
