"""CPU rehearsal of ``bench/run.py``: the shape of a run's result at toy
size, and the refusal to print any result without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import run

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
SEED = 2**33 + 7  # wider than 32 bits: the benchmark takes any seed up to ~2**33


def data(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def rehearse(trace=False, seconds=3.0, on_system=None, seed=SEED, control=False, rate=None):
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = {"name": "tiny.chat", "config": "tiny-dense", "traffic": "tiny_mix", "chips": 1}
    mix = data("tiny_mix.json")
    if rate is not None:
        mix["rate_per_s"] = rate
    return run.run_cell(bench, cell, data("tiny.json"), mix, seed=seed,
                        seconds=seconds, trace=trace, control=control, on_system=on_system)


def test_untraced_run_reports_the_end_to_end_metrics_and_checks_last():
    r = rehearse()
    assert list(r)[:4] == ["correct", "attempted", "failed", "metrics"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] >= 15
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    names = {m["name"] for m in bench["end_to_end"]}
    # peak_hbm_gb needs a device that reports memory; the CPU does not
    assert set(r["metrics"]) == names - {"peak_hbm_gb"}
    assert all(set(v) == {"value", "unit"} for v in r["metrics"].values())
    assert r["device"] == {"platform": "cpu", "kind": "cpu", "count": 1, "memory_peak_bytes": None}
    assert set(r["checks"]) == {"max_logit_gap"}
    assert r["settled_instances"] >= 1
    json.dumps(r)  # one JSON line


def test_same_seed_offers_the_same_requests():
    import loadgen

    mix = data("tiny_mix.json")
    a, wa = loadgen.schedule(mix, SEED, 5.0, 256)
    b, wb = loadgen.schedule(mix, SEED, 5.0, 256)
    c, _ = loadgen.schedule(mix, SEED + 1, 5.0, 256)
    assert wa == wb and [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    # another seed: the same sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == sorted(r.max_new_tokens for r in c)
    assert [r.due_s for r in a] != [r.due_s for r in c]


def test_each_reader_declares_what_the_benchmark_says_of_its_metric():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for m in bench["per_layer"]:
        r = run.load_reader(m["name"])
        assert (r.LAYER, r.UNIT, r.BETTER, r.SOURCE, r.MOVES) == \
            (m["layer"], m["unit"], m["better"], m["source"], m["moves"]), m["name"]


def test_warm_up_serves_every_prefill_size_longest_first():
    import loadgen

    mix = data("tiny_mix.json")
    sizes = [len(r.prompt) for r in loadgen.warmup_requests(mix, SEED, 256, 16)]
    assert sizes == [64, 32, 16, 8]


def test_a_chain_that_never_settles_fails_the_run():
    """With fusion off, every stage but the head runs as eager glue that
    compiles on each call, so no warm-up round is free of compiles."""
    def no_fusion(system):
        system.platform.merger.policy.enabled = False

    with pytest.raises(RuntimeError, match="did not settle"):
        rehearse(on_system=no_fusion)


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "starcoder2-3b.chat", "--seed",
         str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_prints_no_result_and_fails():
    p = _cli(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU" in p.stderr


def test_with_only_the_benchmarks_files_it_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    p = _cli(tmp_path)
    assert p.returncode != 0 and p.stdout.strip() == ""


@pytest.mark.parametrize("fault", ["kv_not_written", "half_batch", "token_altered"])
def test_a_broken_timed_path_reads_not_correct(fault):
    """The timed path broken underneath: a decode step that leaves the KV
    arena as it was, one that serves only every other live request of its
    batch (the rest step from a masked slot), or a token altered where it
    is produced."""
    from repro.serving import continuous

    restore = []

    def break_it(system):
        eng = system.engine
        if fault == "kv_not_written":
            step = eng.paged_decode_step
            eng.paged_decode_step = lambda t, c, bt, write_kv=True: step(t, c, bt, write_kv=False)
        elif fault == "half_batch":
            step = eng.paged_decode_step

            def half(t, c, bt, write_kv=True):
                c = np.array(c)
                c[np.flatnonzero(c)[1::2]] = 0
                return step(t, c, bt, write_kv=write_kv)

            eng.paged_decode_step = half
        else:
            greedy = continuous._greedy_token
            vocab = system.cfg.vocab_size
            continuous._greedy_token = lambda logits: (greedy(logits) + 1) % vocab
            restore.append(lambda: setattr(continuous, "_greedy_token", greedy))

    try:
        # a busier mix, so that every decode batch holds several requests
        r = rehearse(on_system=break_it, seed=SEED + 11, rate=40.0)
    finally:
        for undo in restore:
            undo()
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > r["checks"]["max_logit_gap"]["limit"]


def test_the_fp8_control_reads_not_correct_where_the_program_reads_correct():
    """The control (the reference at fp8, in the program's place) goes
    through the run's own comparison and reads ``correct`` false on the
    same requests on which the program's gaps keep every limit. The toy
    model's limit (``data/tiny.json``) lies between the program's widest
    gap over 12 seeds (0.019) and the control's narrowest (0.046), CPU
    readings."""
    r = rehearse(control=True)
    assert r["correct"] is False
    assert r["checks"]["max_logit_gap"]["value"] > r["checks"]["max_logit_gap"]["limit"]
    for name, c in r["checks"].items():
        assert r[f"program_{name}"] <= c["limit"], name
