"""CPU rehearsal of ``chip_smoke.py``: the same phases at reduced size, with
the Pallas kernels in interpret mode."""
import os

import pytest

from repro.configs import get_arch, reduced_config
from repro.launch import smoke

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_chip_smoke_phases_rehearse_on_cpu(monkeypatch):
    monkeypatch.setenv("REPRO_USE_PALLAS", "1")
    cfg = reduced_config(get_arch("starcoder2-3b"))
    report = smoke.run(cfg, seed=0, requests=4, new_tokens=8, page=16,
                       min_prompt=32, max_prompt=64, timeout_s=300)
    assert smoke.failures(report) == []
    assert report["dispatch_mode"] == "interpret"
    assert report["prompt_lens"][0] == 32 and all(32 <= n <= 64 for n in report["prompt_lens"])
    assert report["tokens_served"] == [8] * 4
    # the chain fused while serving, all the way to one instance
    assert report["merges"] and report["instances_left"] == 1
    paths = report["kernel_paths"]
    for op in smoke.PAGED_OPS:
        assert paths[f"{op}:interpret"] > 0 and f"{op}:ref" not in paths
    assert len(report["checks"]) == 2
    for c in report["checks"]:
        assert c["argmax_served"] == c["argmax_ref"] and c["rel"] <= smoke.LOGIT_TOL


def _report(**over):
    base = {
        "dispatch_mode": "kernel", "new_tokens": 4, "tokens_served": [4, 4],
        "merges": [("a", "b")], "merges_refused": [], "instances_left": 1,
        "kernel_paths": {f"{op}:kernel": 3 for op in smoke.PAGED_OPS},
        "checks": [{"request": 0, "argmax_served": 5, "argmax_ref": 5,
                    "rel": 0.01, "max_abs_diff": 0.1}],
        "peak_bytes": 8 << 30, "bytes_limit": 16 << 30,
    }
    base.update(over)
    return base


@pytest.mark.parametrize("over,needle", [
    ({}, None),
    ({"merges": []}, "no chain merge"),
    ({"instances_left": 2}, "not fused to one"),
    ({"merges_refused": [(("a", "b", "c"), "health check failed: c: ...")]},
     "merge of a + b + c refused"),
    ({"kernel_paths": {"paged_decode_attention:kernel": 1,
                       "paged_chunk_attention:ref": 2}}, "paged_chunk_attention"),
    ({"tokens_served": [4, 3]}, "tokens served"),
    ({"checks": [{"request": 1, "argmax_served": 5, "argmax_ref": 6,
                  "rel": 0.01, "max_abs_diff": 0.1}]}, "argmax"),
    ({"checks": [{"request": 1, "argmax_served": 5, "argmax_ref": 5,
                  "rel": 0.5, "max_abs_diff": 9.0}]}, "logit diff"),
    ({"peak_bytes": 15 << 30}, "peak"),
])
def test_smoke_failures_name_each_failed_phase(over, needle):
    got = smoke.failures(_report(**over))
    if needle is None:
        assert got == []
    else:
        assert got and any(needle in f for f in got)


def test_chip_smoke_main_refuses_without_tpu(capsys, monkeypatch):
    monkeypatch.syspath_prepend(ROOT)
    import chip_smoke

    assert chip_smoke.main([]) != 0
    out, err = capsys.readouterr()
    assert out == ""  # no result line, no fallback run
    assert "no TPU found" in err
