"""The idle split by program span and the health-check reader, on
hand-made traces and spans."""
import dataclasses

import pytest

import host_spans as hs
import run
import trace_reduce as tr
from repro.analysis.dispatch import DispatchSnapshot
from repro.obs import SpanRecord

LINE = "/host:CPU/3: python"


def op(name, start, end):
    return tr.Op(name, start, end - start)


def host(*spans, line=LINE):
    return [(line, s) for s in spans]


# one decode step on the batcher's line: the benchmark's own wrapper
# around the engine call is on the line too, and is no program span
STEP = host(
    op("serve.decode_step", 0, 100),
    op("serve.step_inputs", 0, 10),
    op("bench.call#7", 10, 92),
    op("engine.decode_step", 10, 90),
    op("engine.caches", 10, 30),
    op("platform.dispatch", 30, 80),
    op("platform.execute", 40, 70),
    op("engine.store_back", 80, 90),
    op("serve.token_fetch", 92, 97),
)


def test_a_gap_that_nested_spans_share_goes_to_the_innermost():
    pieces = hs.innermost(hs.program_spans(STEP))
    got = hs.split_gaps([(20, 50), (85, 120)], pieces)
    assert got == {
        "engine.caches": 10, "platform.dispatch": 10, "platform.execute": 10,
        "engine.store_back": 5, "serve.decode_step": 2 + 3, "serve.token_fetch": 5,
        None: 20,
    }


def test_the_program_spans_are_those_of_the_batchers_line():
    other = host(op("engine.caches", 0, 50), op("platform.execute", 60, 70),
                 line="/host:CPU/4: python")
    spans = hs.program_spans(STEP + other)
    assert {s.name for s in spans} == {s.name for _, s in STEP} - {"bench.call#7"}


def test_the_two_shares_sum_to_at_most_the_idle_share():
    # device busy in [0, 12) and [45, 60) of a [0, 200) window: idle gaps
    # [12, 45) and [60, 200), the last partly under no span at all
    ops = [op("fusion.1", 0, 12), op("fusion.2", 45, 60)]
    got = hs.idle_split(ops, STEP, 0, 200)
    idle = 100.0 * (33 + 140) / 200
    assert got["device_idle_share"] == pytest.approx(idle)
    # batcher: serve.decode_step 90..92 and 97..100, token_fetch 92..97;
    # dispatch: caches 12..30, dispatch 30..40 and 70..80, execute 40..45
    # and 60..70, store_back 80..90
    assert got["batcher_idle_share"] == pytest.approx(100.0 * (2 + 3 + 5) / 200)
    assert got["dispatch_idle_share"] == pytest.approx(100.0 * (18 + 20 + 15 + 10) / 200)
    assert got["unattributed_idle_share"] == pytest.approx(100.0 * 100 / 200)
    total = got["batcher_idle_share"] + got["dispatch_idle_share"]
    assert total + got["unattributed_idle_share"] == pytest.approx(idle)
    assert total <= got["device_idle_share"]


def span(name, t0, t1, ph="X"):
    return SpanRecord(0, 2, 0, name, "control-plane", t0, t1, ph)


def test_the_health_checks_sum_by_phase():
    records = [
        span("merge:a+b", 0.0, 10.0),
        span("merge.check", 1.0, 9.0),
        span("merge.replay_ref", 1.0, 2.0),
        span("merge.replay_fused", 2.0, 7.5),
        span("merge.compare", 7.5, 9.0),
        span("merge.check", 11.0, 12.0),
        span("merge:a+b", 12.0, 12.0, ph="i"),
    ]
    got = hs.merge_checks(records)
    assert got == {"checks": 2, "merge.check": 9.0, "merge.replay_ref": 1.0,
                   "merge.replay_fused": 5.5, "merge.compare": 1.5}


def test_merge_check_s_reads_the_sum_at_the_windows_opening():
    reader = run.load_reader("merge_check_s")
    snap = DispatchSnapshot(compiles=3, host_syncs=0, decode_steps=0, kernel_calls=0,
                            compile_s=1.0, merge_check_s=42.5)
    later = dataclasses.replace(snap, merge_check_s=50.0)
    assert reader.read({"counters": {"open": (snap, 1, 4), "close": (later, 1, 4)}}) == 42.5


def test_merge_check_s_is_absent_from_a_program_that_keeps_no_sum():
    @dataclasses.dataclass(frozen=True)
    class OlderSnapshot:  # the dispatch tracer's snapshot before the sum
        compiles: int
        host_syncs: int

    reader = run.load_reader("merge_check_s")
    snap = OlderSnapshot(3, 0)
    assert reader.read({"counters": {"open": (snap, 1, 4), "close": (snap, 1, 4)}}) is None
