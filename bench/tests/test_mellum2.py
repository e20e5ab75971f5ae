"""Mellum2's reference and counts (``tiny-mellum.json``, one period of its
layer pattern at toy widths): the reference draws the deployed weights, the
program's prefill and paged decode agree with its full forward pass in
logits, and the fp8 control, plain RoPE and an unwindowed model do not; the
counts and the two readers of the MoE layer."""
import json
import os
import types

import jax.numpy as jnp
import numpy as np
import pytest

import mellum2_counts as counts
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**33 + 16
PROMPT, STEPS, CHUNK, SLOTS, LIVE = 40, 8, 16, 4, 1

#: |program - reference| over every logit of the served positions. The
#: program computes in bfloat16 and routes on bfloat16 activations: its
#: logits sit within ~0.03 of the float32 reference, and a route flipped
#: at a near tie of the router moves one row's by up to ~0.1 (on three
#: weight seeds; the logits' scale is ~3.5). The fp8 control, plain RoPE on
#: the full layer and full attention on the windowed ones miss by 0.41 or
#: more at the largest logit and by 0.07 or more on the mean.
MAX_ABS, MEAN_ABS = 0.2, 0.03


def tiny():
    with open(os.path.join(DATA, "tiny-mellum.json")) as f:
        return json.load(f)


def flat(tree, prefix=""):
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(flat(v, f"{prefix}{k}."))
        else:
            out[f"{prefix}{k}"] = v
    return out


@pytest.fixture(scope="module")
def served():
    """A 40-token prompt (longer than the 16-token window) prefilled in
    chunks of 16, then 8 greedy decode steps in slot 1 of a 4-slot batch
    whose other slots are empty: the program's logits at positions 39..47
    and the sequence."""
    from repro.configs.base import ModelConfig
    from repro.core import TinyJaxBackend
    from repro.models.model import build_model
    from repro.serving.engine import ServingEngine

    cfg = tiny()
    m, serving = cfg["model"], cfg["serving"]
    platform = TinyJaxBackend()
    engine = ServingEngine(build_model(ModelConfig(name=cfg["name"], **m)), platform,
                           max_len=serving["max_len"], seed=run.weight_seed(SEED),
                           kv_pages=serving["kv_pages"], kv_page_size=serving["kv_page_size"])
    try:
        prompt = np.random.default_rng(SEED).integers(0, m["vocab_size"], (PROMPT,), np.int32)
        job = engine.begin_prefill_paged("a", {"tokens": prompt[None]})
        logits = None
        while logits is None:
            logits = engine.prefill_chunk_paged(job, CHUNK)
        rows, seq = [np.asarray(logits)[0]], list(prompt)
        for _ in range(STEPS):
            seq.append(int(np.argmax(rows[-1])))
            engine.arena.extend("a", len(seq))
            table = np.zeros((SLOTS, engine.block_width), np.int32)
            table[LIVE] = engine.arena.block_row("a", engine.block_width)
            cur, tok = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS, 1), np.int32)
            cur[LIVE], tok[LIVE, 0] = len(seq) - 1, seq[-1]
            rows.append(np.asarray(engine.paged_decode_step(jnp.asarray(tok), cur, table))[LIVE])
        params = {n: flat(platform.spec_of(n).params) for n in engine.chain_names()}
        yield cfg, np.stack(rows), np.asarray(seq, np.int32), params, engine.chain_names()
    finally:
        platform.shutdown()


def test_reference_draws_the_deployed_weights_bit_for_bit(served):
    cfg, _, _, params, names = served
    m, ws = cfg["model"], run.weight_seed(SEED)
    reference = run.model_modules(cfg)[0]
    shared = reference.shared_weights(m, ws)
    assert sorted(params[names[-1]]) == sorted(shared)
    for k in shared:
        np.testing.assert_array_equal(np.asarray(params[names[-1]][k]), np.asarray(shared[k]))
    for i, name in enumerate(names[1:-1]):
        want = reference.stage_weights(m, ws, i)
        assert sorted(params[name]) == sorted(want)
        for k in want:
            assert params[name][k].dtype == want[k].dtype, k
            np.testing.assert_array_equal(np.asarray(params[name][k]), np.asarray(want[k]), err_msg=k)


@pytest.mark.parametrize("variant,agrees", [
    ("published", True),
    ("fp8 control", False),
    ("plain RoPE on the full layer", False),
    ("no window", False),
])
def test_prefill_and_paged_decode_agree_with_the_reference_in_logits(served, variant, agrees):
    cfg, got, seq, _, _ = served
    reference = run.model_modules(cfg)[0]
    m = dict(cfg["model"])
    if variant == "plain RoPE on the full layer":
        m.update(yarn_factor=1.0, yarn_attention_factor=1.0)
    if variant == "no window":
        m.update(sliding_window=1 << 20)
    want = reference.forward_logits(m, run.weight_seed(SEED), seq,
                                    control=variant == "fp8 control")[PROMPT - 1:]
    gap = np.abs(got - want)
    within = float(np.max(gap)) <= MAX_ABS and float(np.mean(gap)) <= MEAN_ABS
    assert within == agrees, (variant, float(np.max(gap)), float(np.mean(gap)))


def _model():
    return tiny()["model"]


@pytest.mark.parametrize("lengths", [[1], [16, 17], [3, 40, 9]])
def test_windowed_counts_read_each_layers_own_keys(lengths):
    m = _model()
    w = m["sliding_window"]
    pairs = counts.paged_decode_kernel(m, lengths)
    assert len(pairs) == m["num_layers"]
    for i, (flops, _) in enumerate(pairs):
        keys = sum(n if i % 4 == 3 else min(n, w) for n in lengths)
        assert flops == 4 * m["num_heads"] * m["d_head"] * keys
    start, rows = 20, 8
    for i, (flops, _) in enumerate(counts.paged_chunk_kernel(m, start, rows)):
        keys = sum(p + 1 if i % 4 == 3 else min(p + 1, w) for p in range(start, start + rows))
        assert flops == 4 * m["num_heads"] * m["d_head"] * keys


def test_moe_counts_take_the_routed_experts_only():
    m = _model()
    d, f, k, e = m["d_model"], m["moe_d_ff"], m["num_experts_per_tok"], m["num_experts"]
    flops, nbytes = counts.moe_gmm_kernel(m, [5, 9, 30])[0]
    assert flops == 2 * 3 * d * f * 3 * k
    touched = e * (1 - (1 - k / e) ** 3)
    assert nbytes == int(3 * (d + f) * 3 * k * 2 + touched * 3 * d * f * 2)
    assert counts.experts_touched(m, 1) == pytest.approx(k)
    assert counts.experts_touched(m, 10_000) == pytest.approx(e)
    h, kv, hd, layers = m["num_heads"], m["num_kv_heads"], m["d_head"], m["num_layers"]
    per_token = 2 * d * h * hd + 2 * d * kv * hd + d * e + k * 3 * d * f
    one_key = 4 * h * hd
    assert counts.decode_step_flops(m, [1]) == \
        2 * (layers * per_token + d * m["vocab_size"]) + layers * one_key


def _reader(name):
    return run.load_reader(name)


def test_experts_touched_share_reads_the_recorded_rows():
    rows_a = [[1, 0, 0, 3], [0, 0, 0, 2]]  # layer 0: 2 of 4 experts; layer 1: 1
    rows_b = [[1, 1, 1, 1], [1, 0, 1, 0]]  # 4 of 4; 2 of 4
    spans = [types.SimpleNamespace(name="moe.expert_rows", args={"rows": r}) for r in (rows_a, rows_b)]
    spans.append(types.SimpleNamespace(name="prefill-stall", args=None))
    got = _reader("experts_touched_share.decode").read({"spans": spans})
    assert got == pytest.approx(100.0 * (0.5 + 0.25 + 1.0 + 0.5) / 4)
    assert _reader("experts_touched_share.decode").read({"spans": spans[2:]}) is None


def test_moe_gmm_roofline_is_silent_without_the_kernel():
    trace = {"devices": [], "marks": [], "calls": {}}
    ctx = {"trace": trace, "peaks": {"bf16_flops_per_s": 1.0, "hbm_bytes_per_s": 1.0}}
    assert _reader("moe_gmm_roofline").read(ctx) is None
