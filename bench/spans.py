"""Reductions of the program's request spans (``obs`` flight recorder)."""
from __future__ import annotations

import math


def p95_ms(spans, cat: str):
    """Nearest-rank 95th percentile, in ms, of the spans of one category;
    None when there are none."""
    durs = sorted(r.t1 - r.t0 for r in spans if r.cat == cat and r.ph == "X")
    if not durs:
        return None
    return 1e3 * durs[max(0, math.ceil(0.95 * len(durs)) - 1)]
