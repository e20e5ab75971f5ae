"""The reduction from trace to busy time, idle gaps and kernel time, on
hand-made intervals and on a trace recorded here; the kernel rooflines and
MFU from the configuration's counts, summed per layer call."""
import os

import jax
import jax.numpy as jnp
import pytest

import device
import flops
import run
import trace_reduce as tr

Op = tr.Op


def test_union_merges_overlaps_and_clips_to_the_window():
    ops = [Op("a", 0, 10), Op("b", 5, 10), Op("c", 30, 10), Op("d", 38, 4), Op("e", 90, 20)]
    assert tr.union_ns(ops, 0, 100) == 15 + 12 + 10
    assert tr.union_ns(ops, 8, 35) == 7 + 5
    assert tr.union_ns([], 0, 100) == 0


def test_idle_gaps_tile_the_window_with_busy_time():
    ops = [Op("a", 10, 10), Op("b", 15, 20), Op("c", 60, 5)]
    gaps = tr.idle_gaps(ops, 0, 100)
    assert gaps == [(65, 100), (35, 60), (0, 10)]  # longest first
    assert sum(e - s for s, e in gaps) + tr.union_ns(ops, 0, 100) == 100


def test_each_op_belongs_to_the_latest_mark_before_it():
    ops = [Op("x", 5, 1), Op("y", 10, 1), Op("z", 25, 1)]
    assert tr.assign(ops, [10, 20]) == [-1, 0, 1]


def test_top_ops_sum_device_time_by_name():
    ops = [Op("fusion", 0, 3), Op("dot", 3, 5), Op("fusion", 8, 4), Op("late", 200, 9)]
    assert tr.top_ops(ops, 0, 100) == [["fusion", 7e-9], ["dot", 5e-9]]


def test_gaps_are_named_by_the_host_event_that_overlaps_most():
    host = [("h/0: main", Op("bench.call#3", 0, 50)), ("h/0: main", Op("other", 0, 10)),
            ("h/1: pool", Op("unrelated", 0, 1000))]
    named = tr.name_gaps([(40, 60), (500, 600)], host, thread_of="bench.call#")
    assert named == [["bench.call", 20e-9], ["no host event", 100e-9]]


TOY = {"family": "dense", "norm": "layernorm", "num_layers": 2, "d_model": 8, "num_heads": 2,
       "num_kv_heads": 1, "d_head": 4, "d_ff": 16, "vocab_size": 32, "act": "gelu"}


def _ctx(calls, marks, ops, kind_ops, m=TOY, peaks=None):
    steps = {}
    for op, i in zip(ops, tr.assign(ops, [s for s, _ in marks])):
        steps.setdefault(i, []).append(op)
    return {"model": m, "counts": flops,
            "peaks": peaks or {"bf16_flops_per_s": 1e9, "hbm_bytes_per_s": 1e9},
            "trace": {"marks": marks, "calls": calls,
                      "devices": [{"plane": "/device:TPU:0", "ops": ops, "steps": steps}]}}


def test_kernel_roofline_is_the_least_time_over_the_kernels_time():
    # one decode call over two sequences of 3 and 5 keys; the kernel ran
    # 20 ns in each of the 2 layers
    ops = [Op("fusion.1", 100, 7), Op("paged_decode_attention.1", 110, 20),
           Op("paged_decode_attention.1", 140, 20)]
    ctx = _ctx({7: ("decode", [3, 5])}, [(100, 7)], ops, None)
    # one layer: 8 keys, q of 2 heads of 4 in and out
    f, b = 4 * 2 * 4 * 8, 2 * 8 * 1 * 4 * 2 + 2 * 2 * 2 * 4 * 2
    need_s = 2 * max(f / 1e9, b / 1e9)
    assert device.kernel_roofline(ctx, "decode", ("paged_decode_attention",),
                                  "paged_decode_kernel") == 100.0 * need_s / 40e-9
    assert device.kernel_roofline(ctx, "prefill", ("paged_chunk_attention",),
                                  "paged_chunk_kernel") is None


def test_step_mfu_counts_the_model_work_over_the_steps_busy_time():
    ops = [Op("a", 1000, 100), Op("b", 1050, 100), Op("c", 2000, 50)]
    ctx = _ctx({0: ("prefill", (0, 3)), 1: ("decode", [4])}, [(1000, 0), (2000, 1)], ops, None)
    work = flops.prefill_chunk_flops(ctx["model"], 0, 3)
    assert device.step_mfu(ctx, "prefill", "prefill_chunk_flops") == 100.0 * work / (150e-9 * 1e9)
    assert device.step_mfu(ctx, "decode", "decode_step_flops") == 100.0 * flops.decode_step_flops(
        ctx["model"], [4]) / (50e-9 * 1e9)


def _served_trace(m, peaks):
    """A reduced trace of 12 decode steps and 4 prefill chunks at the
    model's sizes; each call ran some ops and its kernel (a different time
    each)."""
    calls, marks, ops = {}, [], []
    for k in range(16):
        t = 10_000 * k
        if k % 4 == 3:
            calls[k] = ("prefill", (512 * (k // 4), 300 + 97 * k))
            kernel = "paged_chunk_attention.2"
        else:
            calls[k] = ("decode", [1 + 37 * k, 900 + k, 3000 - 11 * k][: 1 + k % 3])
            kernel = "paged_decode_attention.7"
        marks.append((t, k))
        ops += [Op("fusion.1", t, 800 + 13 * k), Op(kernel, t + 900, 2_000 + 101 * k),
                Op("fusion.2", t + 3_000, 500)]
    return _ctx(calls, marks, ops, None, m=m, peaks=peaks)


def _parent_readings(ctx):
    """The four readings as the harness computed them before each
    configuration named its counts: one layer's kernel work times the
    depth, every formula written out here."""
    m, peaks, t = ctx["model"], ctx["peaks"], ctx["trace"]
    L, d, h, kv, hd, f, v = (m[k] for k in ("num_layers", "d_model", "num_heads", "num_kv_heads",
                                             "d_head", "d_ff", "vocab_size"))
    params = d * h * hd + 2 * d * kv * hd + h * hd * d + (3 if m["act"] == "silu" else 2) * d * f

    def least(fl, nb):
        return max(fl / peaks["bf16_flops_per_s"], nb / peaks["hbm_bytes_per_s"])

    out = {}
    for kind, kernel in (("decode", "paged_decode_attention"), ("prefill", "paged_chunk_attention")):
        work = busy = need = spent = 0
        for i, step_ops in t["devices"][0]["steps"].items():
            what, data = t["calls"][t["marks"][i][1]]
            if what != kind:
                continue
            if kind == "decode":
                keys, rows = sum(data), len(data)
                work += rows * 2 * (L * params + d * v) + L * 4 * h * hd * keys
                layer = (4 * h * hd * keys, 2 * keys * kv * hd * 2 + 2 * rows * h * hd * 2)
            else:
                start, rows = data
                keys = rows * start + rows * (rows + 1) // 2
                work += 2 * L * params * rows + L * 4 * h * hd * keys + 2 * d * v
                layer = (4 * h * hd * keys, 2 * (start + rows) * kv * hd * 2 + 2 * rows * h * hd * 2)
            busy += tr.union_ns(step_ops, min(o.start_ns for o in step_ops),
                                max(o.end_ns for o in step_ops))
            need += L * least(*layer)
            spent += sum(o.dur_ns for o in step_ops if o.name.startswith(kernel))
        out[f"mfu.{kind}"] = 100.0 * work / (busy / 1e9 * peaks["bf16_flops_per_s"])
        name = "paged_decode_roofline" if kind == "decode" else "paged_chunk_roofline"
        out[name] = 100.0 * need / (spent / 1e9)
    return out


READERS = ("mfu.decode", "mfu.prefill", "paged_decode_roofline", "paged_chunk_roofline")


@pytest.mark.parametrize("config", ["starcoder2-3b", "toy"])
def test_per_layer_sums_read_what_one_layer_times_the_depth_read(config):
    """The per-layer-call sum over the configuration's named counts gives
    each reading the harness gave before, to 1e-9 relative: StarCoder2-3B
    with its own counts module and the v5e's peaks, and the hand-made toy
    model of the fixtures above."""
    if config == "toy":
        m, counts, peaks = TOY, flops, None
    else:
        cfg = run.read_json(os.path.join(run.HERE, "configs", f"{config}.json"))
        m, counts, peaks = cfg["model"], run.model_modules(cfg)[1], run.peaks_for("TPU v5 lite")
    ctx = dict(_served_trace(m, peaks), counts=counts)
    want = _parent_readings(ctx)
    for name in READERS:
        assert run.load_reader(name).read(ctx) == pytest.approx(want[name], rel=1e-9), name


def test_a_recorded_trace_loads_with_its_host_annotations(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.trace_window"):
            for k in range(3):
                with jax.profiler.TraceAnnotation(f"bench.call#{k}"):
                    f(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    trace = tr.load(tr.find_xplane(str(tmp_path)))
    names = [ev.name for _, ev in trace.host_events]
    assert "bench.trace_window" in names
    assert sorted(n for n in names if n.startswith("bench.call#")) == [
        "bench.call#0", "bench.call#1", "bench.call#2"]
    win = next(ev for _, ev in trace.host_events if ev.name == "bench.trace_window")
    calls = [ev for _, ev in trace.host_events if ev.name.startswith("bench.call#")]
    assert all(win.start_ns <= c.start_ns and c.end_ns <= win.end_ns for c in calls)
    # the CPU has no device plane; the chip's trace has one per chip
    assert all(p.startswith("/device:") for p in trace.device_ops)
