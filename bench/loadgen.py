"""Open-loop traffic from a mix file and a seed.

A mix file (``bench/traffic/<name>.json``) gives the prompt and output
length distributions, the arrival rate and the lead-in. Every seed gets the
same set of sizes and the same set of gaps between arrivals, in another
order: the lengths are the distribution's quantiles at evenly spaced
probabilities, the gaps those of an exponential distribution (Poisson
arrivals) scaled so that one epoch of requests spans the window exactly. So
two seeds offer the same work, and only its order and the token ids differ.

The stream is two epochs, each of ``rate x span`` requests: a lead-in that
brings the batch to its steady occupancy, then the window's epoch. No
request is due after the window closes.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    index: int
    due_s: float          # seconds after the stream starts
    prompt: np.ndarray    # (t_in,) int32 token ids
    max_new_tokens: int
    in_window: bool


def quantile_lengths(dist: dict, n: int) -> list[int]:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with the
    given median and sigma, clipped to [min, max]."""
    z = NormalDist()
    mu, sigma = math.log(dist["median"]), float(dist["sigma"])
    out = []
    for i in range(n):
        v = math.exp(mu + sigma * z.inv_cdf((i + 0.5) / n))
        out.append(int(min(dist["max"], max(dist["min"], round(v)))))
    return out


def quantile_gaps(n: int, span_s: float) -> list[float]:
    """``n`` exponential quantiles scaled to sum to ``span_s``."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(gaps)
    return [g * scale for g in gaps]


def requests_per_epoch(mix: dict, seconds: float) -> int:
    return max(1, round(mix["rate_per_s"] * seconds))


def schedule(mix: dict, seed: int, seconds: float, vocab: int) -> tuple[list[Request], float]:
    """The whole stream of one run and the window's start (seconds after the
    stream starts): a lead-in epoch of ``lead_in_s`` seconds, then the
    window's epoch of ``seconds``."""
    rng = np.random.default_rng(seed)
    reqs: list[Request] = []
    t = 0.0
    for span, in_window in ((float(mix["lead_in_s"]), False), (seconds, True)):
        m = requests_per_epoch(mix, span)
        prompts = quantile_lengths(mix["prompt_tokens"], m)
        outputs = quantile_lengths(mix["output_tokens"], m)
        gaps = quantile_gaps(m, span)
        p_order, o_order, g_order = rng.permutation(m), rng.permutation(m), rng.permutation(m)
        for j in range(m):
            if j:  # the epoch's first request opens it; its gaps then fill it
                t += gaps[g_order[j - 1]]
            n_in = prompts[p_order[j]]
            reqs.append(Request(
                index=len(reqs), due_s=t,
                prompt=rng.integers(0, vocab, (n_in,), dtype=np.int32),
                max_new_tokens=outputs[o_order[j]], in_window=in_window,
            ))
        t += gaps[g_order[m - 1]]
    window_start = next(r.due_s for r in reqs if r.in_window)
    return reqs, window_start


def warmup_requests(mix: dict, seed: int, vocab: int, page: int) -> list[Request]:
    """One short request per padded prefill size the mix can produce: each
    prompt pads to a power of two, so the mix's range of lengths fixes the
    set of prefill programs. The longest comes first: the fusion policy
    decides on the chain's last edge at its first call, by whether that
    call waited 50 ms or more, and the first prefill's device work is
    what that wait holds besides loading the head's program (see PERF.md,
    Open questions)."""
    rng = np.random.default_rng([seed, 1])
    lo, hi = mix["prompt_tokens"]["min"], mix["prompt_tokens"]["max"]
    sizes, b = [], 1 << (lo - 1).bit_length()
    while True:
        sizes.append(min(b, hi))
        if b >= hi:
            break
        b *= 2
    n_out = int(mix.get("warmup_new_tokens", 4))
    return [Request(index=i, due_s=0.0, prompt=rng.integers(0, vocab, (n,), dtype=np.int32),
                    max_new_tokens=n_out, in_window=False)
            for i, n in enumerate(reversed(sizes))]
