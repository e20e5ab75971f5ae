"""Host phases as profiler annotations (``Tracer.phase``): the serve loop's
and the merge health check's spans, the flight recorder's clock anchor, and
a batcher loop that survives a request whose admission or seating raises."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.analysis.dispatch import TRACER
from repro.configs import get_arch, reduced_config
from repro.core import FunctionSpec, FusionPolicy, TinyJaxBackend
from repro.models.model import build_model
from repro.obs import CLOCK_ANCHOR, CONTROL_TRACE_ID, Tracer, anchor_offset_ns
from repro.obs import trace as obs_trace
from repro.serving import ContinuousBatcher, ServingEngine

PROGRAM = ("serve.", "engine.", "platform.", "obs.")


def _engine(tracing=True):
    cfg = reduced_config(get_arch("llama3.2-1b"))
    platform = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0),
                              tracing=tracing)
    return ServingEngine(build_model(cfg), platform, max_len=64, kv_pages=64, kv_page_size=16)


@pytest.fixture(scope="module")
def engine():
    e = _engine()
    yield e
    e.platform.shutdown()


@pytest.fixture
def annotations(monkeypatch):
    """Names of the annotations the tracer constructs while the test runs."""
    names = []
    real = obs_trace.TraceAnnotation

    def counted(name, **args):
        names.append(name)
        return real(name, **args)

    monkeypatch.setattr(obs_trace, "TraceAnnotation", counted)
    return names


def _prompt(n, start=3):
    return jnp.asarray((np.arange(n) % 50 + start)[None, :], jnp.int32)


def _serve_one_by_one(engine, prompts, gen=4, **kw):
    cb = ContinuousBatcher(engine, capacity=2, **kw)
    try:
        return [cb.submit({"tokens": p}, gen).result(timeout=300) for p in prompts]
    finally:
        cb.shutdown()


def test_a_disabled_tracer_constructs_no_annotation_on_the_serve_path(annotations):
    off = Tracer(enabled=False)
    assert off.phase("serve.decode_step") is off.phase("merge.check", control=True, k=1)
    e = _engine(tracing=False)
    try:
        out = _serve_one_by_one(e, [_prompt(9), _prompt(5)])
    finally:
        e.platform.shutdown()
    assert all(len(r["tokens"][0]) == 4 for r in out)
    assert annotations == []


def test_step_phases_are_annotations_and_never_span_records(engine, annotations):
    engine.platform.tracer.recorder.clear()
    _serve_one_by_one(engine, [_prompt(9), _prompt(6)])
    for name in ("serve.admit", "serve.prefill_chunk", "serve.decode_step",
                 "serve.step_inputs", "serve.token_fetch", "serve.emit",
                 "engine.prefill_chunk", "engine.decode_step", "engine.caches",
                 "engine.store_back", "platform.dispatch", "platform.enter",
                 "platform.execute", "platform.exit", CLOCK_ANCHOR):
        assert name in annotations, name
    records = engine.platform.tracer.recorder.snapshot()
    assert records and not [r for r in records if r.name.startswith(PROGRAM)]


def _profile(tmp_path, engine, prompts, **kw):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    engine.platform.tracer.recorder.clear()
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _serve_one_by_one(engine, prompts, **kw)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    return path


def _program_lines(path):
    """{line: [(name, start_ns, end_ns)]} of the host lines holding the
    program's spans."""
    out = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns) for ev in line.events
                   if ev.name.startswith(PROGRAM)]
            if evs:
                out[(plane.name, i)] = evs
    return out


def _inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


def test_a_profiled_batcher_nests_its_phases_on_one_host_line(engine, tmp_path):
    path = _profile(tmp_path, engine, [_prompt(9, 11), _prompt(6, 13)])
    lines = _program_lines(path)
    assert len(lines) == 1, list(lines)
    (evs,) = lines.values()
    by = {}
    for ev in evs:
        by.setdefault(ev[0], []).append(ev)
    engine_calls = ("engine.decode_step", "engine.prefill_chunk")
    nesting = {
        "serve.step_inputs": ("serve.decode_step",),
        "serve.token_fetch": ("serve.decode_step",),
        "serve.emit": ("serve.decode_step",),
        # a whole-prompt prefix hit reads its first token by a decode step
        "engine.decode_step": ("serve.decode_step", "serve.prefill_chunk"),
        "engine.prefill_chunk": ("serve.prefill_chunk",),
        "engine.caches": engine_calls,
        "engine.store_back": engine_calls,
        "platform.dispatch": engine_calls,
        "platform.execute": ("platform.dispatch",),
        "platform.enter": ("platform.dispatch",),
        "platform.exit": ("platform.dispatch",),
    }
    for child, parents in nesting.items():
        outer = [ev for p in parents for ev in by.get(p, [])]
        assert by[child] and all(_inside(c, outer) for c in by[child]), child


def test_the_clock_anchor_maps_request_spans_onto_the_trace(engine, tmp_path):
    """Shifted by the anchors' offset, each request's ``prefill-stall``
    encloses the engine's chunk annotations it waited on, and each
    ``prefill-chunk`` span lines up with its annotation, to within 1 ms."""
    path = _profile(tmp_path, engine, [_prompt(10), _prompt(11, 7)], prefill_chunk=4)
    offset = anchor_offset_ns(path)
    assert offset is not None
    (evs,) = _program_lines(path).values()
    chunks = sorted((s, e) for name, s, e in evs if name == "engine.prefill_chunk")
    assert len(chunks) == 3 + 3
    records = engine.platform.tracer.recorder.snapshot()
    shift = lambda r: (r.t0 * 1e9 + offset, r.t1 * 1e9 + offset)  # noqa: E731
    stalls = sorted(shift(r) for r in records if r.cat == "prefill-stall")
    assert len(stalls) == 2
    ms = 1e6
    for (s0, s1), mine in zip(stalls, (chunks[:3], chunks[3:])):
        assert all(s0 - ms <= c0 and c1 <= s1 + ms for c0, c1 in mine)
    spans = sorted(shift(r) for r in records if r.cat == "prefill-chunk")
    for (r0, r1), (c0, c1) in zip(spans, chunks, strict=True):
        assert abs(r0 - c0) < ms and abs(r1 - c1) < ms


@pytest.mark.parametrize("where", ["_seat", "_admit_one"])
def test_a_request_whose_admission_raises_fails_alone(engine, where):
    cb = ContinuousBatcher(engine, capacity=2)
    real = getattr(cb, where)
    raised = []

    def raise_once(*args):
        if not raised:
            raised.append(True)
            raise RuntimeError(f"{where} failed")
        return real(*args)

    setattr(cb, where, raise_once)
    try:
        first = cb.submit({"tokens": _prompt(9)}, 4)
        with pytest.raises(RuntimeError, match="failed"):
            first.result(timeout=300)
        second = cb.submit({"tokens": _prompt(7)}, 4).result(timeout=300)
        assert len(second["tokens"][0]) == 4
        assert cb._thread.is_alive()
    finally:
        cb.shutdown()
    engine.arena.check_consistency()
    assert engine.arena.used_pages() == 0


def _deploy_pair(platform):
    w = jnp.eye(8) * 0.5

    def fn_b(ctx, params, x):
        return jnp.tanh(x @ params)

    def fn_a(ctx, params, x):
        return ctx.call("B", x @ params)

    platform.deploy(FunctionSpec("A", fn_a, w))
    platform.deploy(FunctionSpec("B", fn_b, w))


def _within(inner, outer):
    return outer.t0 <= inner.t0 and inner.t1 <= outer.t1


def test_health_check_phases_nest_inside_the_merge_and_sum_in_the_tracer():
    p = TinyJaxBackend(FusionPolicy(min_observations=2, merge_cost_s=0.0))
    TRACER.arm()
    try:
        before = TRACER.snapshot()
        _deploy_pair(p)
        for _ in range(6):
            p.invoke("A", jnp.ones((2, 8)))
        p.merger.wait_idle()
        summed = TRACER.delta(before).merge_check_s
        assert [m for m in p.merger.merge_log if m.healthy]
        event = p.merger.split(frozenset({"A", "B"}), [{"A"}, {"B"}])
        assert event is not None and event.checked_members
    finally:
        TRACER.disarm()
        p.shutdown()
    control = [r for r in p.tracer.recorder.snapshot()
               if r.trace_id == CONTROL_TRACE_ID and r.ph == "X"]
    merges = [r for r in control if r.name == "merge:A+B"]
    checks = [r for r in control if r.name == "merge.check"]
    merge_checks = [c for c in checks if c.args["kind"] == "merge"]
    assert len(merges) == 1 and len(merge_checks) == 1
    (check,) = merge_checks
    assert _within(check, merges[0])
    assert check.args["members"] == "A+B" and check.args["canary_bytes"] > 0
    assert summed == pytest.approx(check.dur_s, rel=0.05, abs=1e-3)
    for kind, replay in (("merge", "merge.replay_fused"), ("split", "merge.replay_split")):
        (c,) = [x for x in checks if x.args["kind"] == kind]
        phases = [r for r in control if r.name in ("merge.replay_ref", replay, "merge.compare")
                  and _within(r, c)]
        members = {r.args["member"] for r in phases}
        assert members and len(phases) == 3 * len(members), kind
