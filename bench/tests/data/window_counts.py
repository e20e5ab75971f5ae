"""A second family's counts, for the tests: the configuration file
``tiny-window.json`` names it. A windowed layer's query attends to at most
``sliding_window`` keys, and its kernel reads only those; every
``full_attention_every``-th layer attends to all. Each kernel function
gives one (flops, bytes) pair per layer, so the two kinds count apart."""
from __future__ import annotations

BF16 = 2  # bytes


def _spans(m: dict) -> list[int | None]:
    """Each layer's window, None for a full layer."""
    every, w = m["full_attention_every"], m["sliding_window"]
    return [None if (i + 1) % every == 0 else w for i in range(m["num_layers"])]


def _keys(window, start: int, rows: int) -> int:
    """Keys attended by query rows start..start+rows-1 together."""
    return sum(p + 1 if window is None else min(p + 1, window) for p in range(start, start + rows))


def _read(window, start: int, rows: int) -> int:
    """Keys the kernel reads for those rows: from the first key the first
    row sees to the last row."""
    first = 0 if window is None else max(0, start - window + 1)
    return start + rows - first


def _matmul_params(m: dict) -> int:
    d, h, kv, hd, f = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"], m["d_ff"]
    return 2 * d * h * hd + 2 * d * kv * hd + 3 * d * f


def _attn(m: dict, keys: int) -> int:
    return 4 * m["num_heads"] * m["d_head"] * keys


def _io(m: dict, keys: int, rows: int) -> int:
    kv, h, hd = m["num_kv_heads"], m["num_heads"], m["d_head"]
    return 2 * keys * kv * hd * BF16 + 2 * rows * h * hd * BF16


def decode_step_flops(m: dict, lengths: list[int]) -> int:
    body = 2 * len(lengths) * (m["num_layers"] * _matmul_params(m) + m["d_model"] * m["vocab_size"])
    return body + sum(_attn(m, _keys(w, n - 1, 1)) for w in _spans(m) for n in lengths)


def prefill_chunk_flops(m: dict, start: int, rows: int) -> int:
    body = 2 * m["num_layers"] * _matmul_params(m) * rows + 2 * m["d_model"] * m["vocab_size"]
    return body + sum(_attn(m, _keys(w, start, rows)) for w in _spans(m))


def paged_decode_kernel(m: dict, lengths: list[int]) -> list[tuple[int, int]]:
    out = []
    for w in _spans(m):
        keys = sum(_keys(w, n - 1, 1) for n in lengths)
        out.append((_attn(m, keys), _io(m, keys, len(lengths))))
    return out


def paged_chunk_kernel(m: dict, start: int, rows: int) -> list[tuple[int, int]]:
    return [(_attn(m, _keys(w, start, rows)), _io(m, _read(w, start, rows), rows))
            for w in _spans(m)]
