"""Operations and bytes of Mellum2-12B-A2.5B's served work, from shapes and
lengths. The configuration file ``bench/configs/mellum2-12b-a2.5b.json``
names this module under ``"counts"``; the readers call its functions by
name (see ``flops.py`` for the contract).

Layer i is full when ``i % full_attention_every == full_attention_offset``
and attends to every earlier key; every other layer to at most
``sliding_window`` keys, and its kernel reads only those. Every layer is
sparse: a token multiplies through its router and its ``num_experts_per_tok``
experts (three matrices of ``d_model x moe_d_ff`` each), not the other
experts. Each kernel function gives one (flops, bytes) pair per layer, in
the model's layer order, so the two kinds of layer count apart.

``moe_gmm_kernel`` counts the expert layer's three grouped matmuls. Its
bytes are the rows read and written plus the weights of the experts the
step's tokens touch, taken as the expected number of distinct experts when
each token picks its experts uniformly at random, ``E (1 - (1 - k/E)^n)``.
That holds for near-uniform routing, as on the random weights the
benchmark draws; it is the same count whatever implements the layer, and a
kernel that reads every expert's weights spends the rest as its waste.
"""
from __future__ import annotations

BF16 = 2  # bytes


def _windows(m: dict) -> list[int | None]:
    """Each layer's window, None for a full layer."""
    every, offset, w = m["full_attention_every"], m["full_attention_offset"], m["sliding_window"]
    return [None if i % every == offset else w for i in range(m["num_layers"])]


def _keys(window, start: int, rows: int) -> int:
    """Keys attended by query rows at positions start..start+rows-1."""
    def upto(p):  # sum over q < p of min(q + 1, window)
        if window is None or p <= window:
            return p * (p + 1) // 2
        return window * (window + 1) // 2 + (p - window) * window
    return upto(start + rows) - upto(start)


def _read(window, start: int, rows: int) -> int:
    """Keys the kernel reads for those rows: from the first key the first
    row sees to the last row."""
    first = 0 if window is None else max(0, start - window + 1)
    return start + rows - first


def _attn_params(m: dict) -> int:
    d, h, kv, hd = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"]
    return 2 * d * h * hd + 2 * d * kv * hd


def _expert_params(m: dict) -> int:
    """Weights of one expert: gate, up and down."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def _token_params(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    return (_attn_params(m) + m["d_model"] * m["num_experts"]
            + m["num_experts_per_tok"] * _expert_params(m))


def _attn(m: dict, keys: int) -> int:
    return 4 * m["num_heads"] * m["d_head"] * keys


def _io(m: dict, keys: int, rows: int) -> int:
    kv, h, hd = m["num_kv_heads"], m["num_heads"], m["d_head"]
    return 2 * keys * kv * hd * BF16 + 2 * rows * h * hd * BF16


def decode_step_flops(m: dict, lengths: list[int]) -> int:
    """A decode step of the whole model for the active sequences; each
    length counts the token written this step."""
    n = len(lengths)
    body = 2 * n * (m["num_layers"] * _token_params(m) + m["d_model"] * m["vocab_size"])
    return body + sum(_attn(m, _keys(w, ln - 1, 1)) for w in _windows(m) for ln in lengths)


def prefill_chunk_flops(m: dict, start: int, rows: int) -> int:
    """A prefill chunk of ``rows`` real prompt tokens from ``start``; the
    head runs on the last row only."""
    body = 2 * m["num_layers"] * _token_params(m) * rows + 2 * m["d_model"] * m["vocab_size"]
    return body + sum(_attn(m, _keys(w, start, rows)) for w in _windows(m))


def paged_decode_kernel(m: dict, lengths: list[int]) -> list[tuple[int, int]]:
    out = []
    for w in _windows(m):
        keys = sum(_keys(w, ln - 1, 1) for ln in lengths)
        out.append((_attn(m, keys), _io(m, keys, len(lengths))))
    return out


def paged_chunk_kernel(m: dict, start: int, rows: int) -> list[tuple[int, int]]:
    return [(_attn(m, _keys(w, start, rows)), _io(m, _read(w, start, rows), rows))
            for w in _windows(m)]


def experts_touched(m: dict, tokens: int) -> float:
    """Expected distinct experts ``tokens`` tokens route to, each picking
    ``num_experts_per_tok`` of ``num_experts`` uniformly."""
    e, k = m["num_experts"], m["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def moe_gmm_kernel(m: dict, lengths: list[int]) -> list[tuple[int, int]]:
    """(flops, bytes) of each layer's three expert matmuls in a decode step
    of ``len(lengths)`` tokens: 2 d f FLOPs a matrix a route; rows in and
    out (gate and up read d and write f, down reads f and writes d) and
    the touched experts' weights."""
    n, d, f, k = len(lengths), m["d_model"], m["moe_d_ff"], m["num_experts_per_tok"]
    flops = 2 * 3 * d * f * n * k
    rows = 3 * (d + f) * n * k * BF16
    weights = experts_touched(m, n) * _expert_params(m) * BF16
    return [(flops, int(rows + weights))] * m["num_layers"]
