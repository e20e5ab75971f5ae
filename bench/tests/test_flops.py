"""The dense counts against hand counts at published sizes: StarCoder2-3B
(GQA, a two-matrix GELU MLP) as the benchmark runs it, and StableLM-2-1.6B
(MHA, a gated SiLU MLP) for the other shape; the roofline's least time."""
import json
import os

import pytest

import device
import flops

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


#: StableLM-2-1.6B's sizes (huggingface.co/stabilityai/stablelm-2-1_6b)
STABLELM = {"family": "dense", "norm": "layernorm", "num_layers": 24, "d_model": 2048,
            "num_heads": 32, "num_kv_heads": 32, "d_head": 64, "d_ff": 5632,
            "vocab_size": 100352, "act": "silu"}


def model(name):
    if name == "stablelm-1.6b":
        return STABLELM
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)["model"]


def test_starcoder2_layer_and_model_parameters():
    m = model("starcoder2-3b")
    # q and o are 3072 x (24 x 128); k and v 3072 x (2 x 128); the MLP two
    # 3072 x 12288 matrices
    per_layer = 3072 * 3072 * 2 + 3072 * 256 * 2 + 3072 * 12288 * 2
    assert flops.layer_matmul_params(m) == per_layer == 95_944_704
    norms = 30 * 4 * 3072 + 2 * 3072
    total = 30 * per_layer + 2 * 49152 * 3072 + norms
    assert total == 3_180_705_792  # the parameter count the config file states


def test_stablelm_gated_mlp_counts_three_matrices():
    m = model("stablelm-1.6b")
    assert flops.layer_matmul_params(m) == 4 * 2048 * 2048 + 3 * 2048 * 5632


def test_causal_keys():
    assert flops.causal_keys(0, 4) == 1 + 2 + 3 + 4
    assert flops.causal_keys(8, 4) == 9 + 10 + 11 + 12


def test_decode_step_flops_by_hand():
    m = model("starcoder2-3b")
    lengths = [10, 20]
    matmul = 2 * 2 * (30 * 95_944_704 + 3072 * 49152)
    attn = 30 * 4 * 24 * 128 * 30  # QK^T and PV over 30 keys in all, 30 layers
    assert flops.decode_step_flops(m, lengths) == matmul + attn


def test_prefill_chunk_flops_by_hand():
    m = model("stablelm-1.6b")
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    rows, start = 100, 28
    keys = sum(start + i + 1 for i in range(rows))
    want = (2 * 24 * per_layer * rows + 24 * 4 * 32 * 64 * keys + 2 * 2048 * 100352)
    assert flops.prefill_chunk_flops(m, start, rows) == want


def test_paged_decode_kernel_reads_each_live_key_and_value_once():
    m = model("starcoder2-3b")
    pairs = flops.paged_decode_kernel(m, [100, 28])
    # K and V of 128 keys over 2 KV heads of 128, bf16, plus q in and out,
    # in each of the 30 layers alike
    assert pairs == [(4 * 24 * 128 * 128, 2 * 128 * 2 * 128 * 2 + 2 * 2 * 24 * 128 * 2)] * 30


def test_paged_chunk_kernel_reads_the_prefix_and_chunk():
    m = model("stablelm-1.6b")
    pairs = flops.paged_chunk_kernel(m, 256, 128)
    assert len(pairs) == 24 and len(set(pairs)) == 1
    f, b = pairs[0]
    assert f == 4 * 32 * 64 * flops.causal_keys(256, 128)
    assert b == 2 * 384 * 32 * 64 * 2 + 2 * 128 * 32 * 64 * 2


@pytest.mark.parametrize("f,b,bound", [(197e12, 1.0, 1.0), (1.0, 819e9 * 2, 2.0)])
def test_roofline_takes_the_larger_bound(f, b, bound):
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert device.roofline_seconds(f, b, peaks) == pytest.approx(bound)
