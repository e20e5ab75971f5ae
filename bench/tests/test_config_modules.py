"""Each configuration names its own reference and counts modules: the
harness refuses a configuration that names neither, compares the named
reference's gaps and reads the named counts. ``data/tiny-window.json`` is a
second family (RMSNorm blocks, sliding windows) that needs only its own
files; the dense reference and counts refuse what they do not describe."""
import os
import types

import numpy as np
import pytest

import flops
import loadgen
import run
import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
SEED = 2**33 + 21


def config(name):
    return run.read_json(os.path.join(DATA, f"{name}.json"))


@pytest.mark.parametrize("key", ["reference", "counts"])
def test_a_configuration_that_names_no_module_is_refused(key):
    cfg = config("tiny")
    del cfg[key]
    with pytest.raises(ValueError, match=f"names no '{key}' module"):
        run.model_modules(cfg)
    # a run of it is refused before anything is built
    bench = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = {"name": "tiny.chat", "config": "tiny-dense", "traffic": "tiny_mix", "chips": 1}
    with pytest.raises(ValueError, match=f"names no '{key}' module"):
        run.run_cell(bench, cell, cfg, config("tiny_mix"), seed=SEED, seconds=1.0, trace=False)


def test_the_configuration_names_the_modules_it_is_checked_and_counted_by():
    reference, counts = run.model_modules(config("tiny-window"))
    assert reference.__file__ == os.path.join(DATA, "window_reference.py")
    assert counts.__file__ == os.path.join(DATA, "window_counts.py")
    dense = run.model_modules(config("tiny"))
    assert dense[0].__file__ == os.path.join(run.HERE, "reference.py")
    assert dense[1].__file__ == os.path.join(run.HERE, "flops.py")


def _sample(m, seed):
    rng = np.random.default_rng(seed)
    out = []
    for i, (n, k) in enumerate([(9, 12), (40, 20), (30, 5)]):
        req = loadgen.Request(i, 0.0, rng.integers(0, m["vocab_size"], (n,), dtype=np.int32), k, True)
        s = run.Served(req, 0.0)
        s.tokens = [int(t) for t in rng.integers(0, m["vocab_size"], (k,))]
        out.append(s)
    return out


@pytest.mark.parametrize("control", [False, True])
def test_check_compares_the_gaps_of_the_reference_the_configuration_names(control):
    cfg = config("tiny-window")
    m = cfg["model"]
    reference, _ = run.model_modules(cfg)
    sample = _sample(m, 5)
    seqs = [(s.req.prompt, np.asarray(s.tokens, np.int32)) for s in sample]
    want = reference.served_gaps(m, run.weight_seed(SEED), seqs, control=control)
    if control:  # the control's gaps are compared, the program's reported beside
        want = [g[1] for g in want]
    got = run.check(reference, m, SEED, sample, cfg["limits"], control)
    assert got["max_logit_gap"] == max(float(np.max(g)) for g in want)
    assert got["mean_logit_gap"] == float(np.mean(np.concatenate(want)))
    assert got["checks"] == {"max_logit_gap": {"value": got["max_logit_gap"],
                                               "limit": cfg["limits"]["max_logit_gap"]}}
    # its windowed model is not of the dense family, which refuses it
    dense, _ = run.model_modules(config("tiny"))
    with pytest.raises(ValueError, match="family"):
        run.check(dense, m, SEED, sample, cfg["limits"], control)


def test_the_window_reaches_the_reference_only_on_its_layers():
    """Served tokens past the window read other gaps than with every layer
    full; within the window the windowed layers read as full ones."""
    cfg = config("tiny-window")
    m = cfg["model"]
    reference, _ = run.model_modules(cfg)
    rng = np.random.default_rng(2)
    short = [(rng.integers(0, 256, (6,), dtype=np.int32), rng.integers(0, 256, (8,), dtype=np.int32))]
    long = [(rng.integers(0, 256, (40,), dtype=np.int32), rng.integers(0, 256, (8,), dtype=np.int32))]
    full = dict(m, full_attention_every=1)
    ws = run.weight_seed(SEED)
    np.testing.assert_array_equal(reference.served_gaps(m, ws, short)[0],
                                  reference.served_gaps(full, ws, short)[0])
    assert not np.array_equal(reference.served_gaps(m, ws, long)[0],
                              reference.served_gaps(full, ws, long)[0])


def _trace_ctx(m, counts):
    """One decode step over sequences of 3 and 40 keys and one prefill
    chunk of 24 rows from position 8; the kernels ran 100 and 200 ns."""
    calls = {0: ("decode", [3, 40]), 1: ("prefill", (8, 24))}
    ops = [tr.Op("fusion.1", 0, 50), tr.Op("paged_decode_attention.1", 50, 100),
           tr.Op("fusion.2", 1000, 300), tr.Op("paged_chunk_attention.1", 1300, 200)]
    steps = {0: ops[:2], 1: ops[2:]}
    return {"model": m, "counts": counts, "peaks": {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
            "trace": {"marks": [(0, 0), (1000, 1)], "calls": calls,
                      "devices": [{"plane": "/device:TPU:0", "ops": ops, "steps": steps}]}}


def test_the_readers_count_windowed_and_full_layers_apart():
    """tiny-window's counts: layers 0 and 2 attend to at most 16 keys, 1
    and 3 to all; the readers sum what each layer needed."""
    cfg = config("tiny-window")
    m = cfg["model"]
    ctx = _trace_ctx(m, run.model_modules(cfg)[1])
    h, kv, hd = 4, 2, 16
    # decode: 3 + 16 keys on a windowed layer, 3 + 40 on a full one
    layers = [(4 * h * hd * keys, 2 * keys * kv * hd * 2 + 2 * 2 * h * hd * 2)
              for keys in (19, 43, 19, 43)]
    need = sum(max(f, b) for f, b in layers) / 1e9
    assert run.load_reader("paged_decode_roofline").read(ctx) == pytest.approx(
        100.0 * need / 100e-9, rel=1e-12)
    # prefill rows 8..31: a windowed layer's row at position p sees
    # min(p + 1, 16) keys and the chunk reads keys 0..31 (the first row's
    # window starts at 0)
    win = sum(min(p + 1, 16) for p in range(8, 32))
    full = sum(p + 1 for p in range(8, 32))
    layers = [(4 * h * hd * keys, 2 * 32 * kv * hd * 2 + 2 * 24 * h * hd * 2)
              for keys in (win, full, win, full)]
    need = sum(max(f, b) for f, b in layers) / 1e9
    assert run.load_reader("paged_chunk_roofline").read(ctx) == pytest.approx(
        100.0 * need / 200e-9, rel=1e-12)
    params = 2 * 64 * 64 + 2 * 64 * 32 + 3 * 64 * 128
    work = 2 * 2 * (4 * params + 64 * 256) + 4 * h * hd * (19 + 43 + 19 + 43)
    assert run.load_reader("mfu.decode").read(ctx) == pytest.approx(
        100.0 * work / (150e-9 * 1e9), rel=1e-12)


def _doubled(module):
    """A counts module whose every count is twice ``module``'s."""
    return types.SimpleNamespace(
        decode_step_flops=lambda m, n: 2 * module.decode_step_flops(m, n),
        prefill_chunk_flops=lambda m, s, r: 2 * module.prefill_chunk_flops(m, s, r),
        paged_decode_kernel=lambda m, n: [(2 * f, 2 * b) for f, b in module.paged_decode_kernel(m, n)],
        paged_chunk_kernel=lambda m, s, r: [(2 * f, 2 * b)
                                            for f, b in module.paged_chunk_kernel(m, s, r)])


@pytest.mark.parametrize("reader", ["mfu.decode", "mfu.prefill", "paged_decode_roofline",
                                    "paged_chunk_roofline"])
def test_doubled_counts_double_the_reading_on_the_same_trace(reader):
    m = config("tiny")["model"]
    once = run.load_reader(reader).read(_trace_ctx(m, flops))
    twice = run.load_reader(reader).read(_trace_ctx(m, _doubled(flops)))
    assert once > 0 and twice == 2 * once


NOT_DENSE = [("num_experts", 64), ("norm", "rmsnorm"), ("qk_norm", True), ("family", "moe"),
             ("act", "relu")]


@pytest.mark.parametrize("key,value", NOT_DENSE)
def test_the_dense_reference_refuses_a_model_it_does_not_describe(key, value):
    cfg = config("tiny")
    m = dict(cfg["model"], **{key: value})
    reference, _ = run.model_modules(cfg)
    seqs = [(np.arange(5, dtype=np.int32), np.arange(3, dtype=np.int32))]
    for call in (lambda: reference.served_gaps(m, 1, seqs), lambda: reference.shared_weights(m, 1),
                 lambda: reference.stage_weights(m, 1, 0)):
        with pytest.raises(ValueError, match=key):
            call()


@pytest.mark.parametrize("key,value", NOT_DENSE)
def test_the_dense_counts_refuse_a_model_they_do_not_describe(key, value):
    cfg = config("tiny")
    m = dict(cfg["model"], **{key: value})
    _, counts = run.model_modules(cfg)
    for call in (lambda: counts.decode_step_flops(m, [4]), lambda: counts.prefill_chunk_flops(m, 0, 4),
                 lambda: counts.paged_decode_kernel(m, [4]), lambda: counts.paged_chunk_kernel(m, 0, 4)):
        with pytest.raises(ValueError, match=key):
            call()
