"""Operations and bytes that the served work of the dense family needs,
from shapes and lengths.

A configuration file names this module under ``"counts"`` when its model is
the dense family (every layer alike: full causal attention with grouped KV
heads and a dense MLP), and it refuses any other (``require_dense``). The
readers reach it as ``ctx["counts"]`` and call these functions by name:

- ``decode_step_flops(m, lengths)`` and ``prefill_chunk_flops(m, start,
  rows)``: a whole step's model FLOPs, for the ``mfu.*`` readings;
- ``paged_decode_kernel(m, lengths)`` and ``paged_chunk_kernel(m, start,
  rows)``: one (flops, bytes) pair for each layer the call ran its kernel
  in, for the kernel rooflines.

A configuration whose layers differ (experts, sliding windows) gives its own
module with these names. These are the yardstick for the roofline and MFU
readings: counted from the model's published sizes and the lengths each
call served, never from what a kernel happens to compute (padding, masked
slots and re-read pages are the kernel's waste, not the work). Non-matmul
operations (norms, rotary, softmax) are left out, as MFU conventionally
does.
"""
from __future__ import annotations

BF16 = 2  # bytes


#: what the dense equations and counts describe, by key of the model dict
DENSE = {"family": ("dense",), "norm": ("layernorm",), "num_experts": (0,),
         "qk_norm": (False,), "act": ("gelu", "silu")}
#: the program's defaults for the keys a model dict may leave out
DEFAULTS = {"norm": "rmsnorm", "num_experts": 0, "qk_norm": False}


def require_dense(m: dict) -> None:
    """Refuse a model dict that the dense equations and counts do not
    describe, naming the key."""
    for key, described in DENSE.items():
        value = m.get(key, DEFAULTS.get(key))
        if value not in described:
            raise ValueError(f"{key} {value!r}: the dense reference and counts describe "
                             f"{key} {' or '.join(map(repr, described))} only")


def layer_matmul_params(m: dict) -> int:
    """Weights one token multiplies through in one layer: q, k, v, o and
    the MLP (three matrices for a gated SiLU MLP, two otherwise)."""
    require_dense(m)
    d, h, kv, hd, f = m["d_model"], m["num_heads"], m["num_kv_heads"], m["d_head"], m["d_ff"]
    mlp = (3 if m["act"] == "silu" else 2) * d * f
    return d * h * hd + 2 * d * kv * hd + h * hd * d + mlp


def attention_flops(m: dict, q_rows: int, keys_total: int) -> int:
    """QK^T and PV of all heads: 4 * H * hd per (query row, key) pair;
    ``keys_total`` sums, over the rows, the keys each attends to."""
    del q_rows
    return 4 * m["num_heads"] * m["d_head"] * keys_total


def causal_keys(start: int, rows: int) -> int:
    """Keys attended by ``rows`` query rows at positions start..start+rows-1
    under a causal mask: row i sees start + i + 1 keys."""
    return rows * start + rows * (rows + 1) // 2


def decode_step_flops(m: dict, lengths: list[int]) -> int:
    """A decode step of the whole model for the active sequences; each
    length counts the token written this step (its attention span)."""
    n = len(lengths)
    per_tok = 2 * (m["num_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"])
    return n * per_tok + m["num_layers"] * attention_flops(m, n, sum(lengths))


def prefill_chunk_flops(m: dict, start: int, rows: int) -> int:
    """A prefill chunk of ``rows`` real prompt tokens from position
    ``start``; the head runs on the last row only."""
    body = 2 * m["num_layers"] * layer_matmul_params(m) * rows
    attn = m["num_layers"] * attention_flops(m, rows, causal_keys(start, rows))
    return body + attn + 2 * m["d_model"] * m["vocab_size"]


def paged_decode_kernel(m: dict, lengths: list[int]) -> list[tuple[int, int]]:
    """(flops, bytes) of each layer's paged decode attention for the active
    sequences: every live key and value read once, q read and the output
    written once per sequence; every layer alike."""
    require_dense(m)
    kv, hd, h = m["num_kv_heads"], m["d_head"], m["num_heads"]
    keys = sum(lengths)
    flops = attention_flops(m, len(lengths), keys)
    nbytes = 2 * keys * kv * hd * BF16 + 2 * len(lengths) * h * hd * BF16
    return [(flops, nbytes)] * m["num_layers"]


def paged_chunk_kernel(m: dict, start: int, rows: int) -> list[tuple[int, int]]:
    """(flops, bytes) of each layer's chunked-prefill attention: causal
    scores of the real rows, the keys and values of the prefix and chunk
    read once, q read and the output written once; every layer alike."""
    require_dense(m)
    kv, hd, h = m["num_kv_heads"], m["d_head"], m["num_heads"]
    flops = attention_flops(m, rows, causal_keys(start, rows))
    nbytes = 2 * (start + rows) * kv * hd * BF16 + 2 * rows * h * hd * BF16
    return [(flops, nbytes)] * m["num_layers"]
