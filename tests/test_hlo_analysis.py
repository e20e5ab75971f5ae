"""Validate the loop-aware HLO cost analyzer against known-FLOPs programs.

These tests compile tiny programs in a SUBPROCESS with a forced multi-device
host platform (the test process itself must keep the default 1-device view).
"""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    import sys
    sys.path.insert(0, "src")
    from repro.launch.hlo_analysis import analyze

    mesh = jax.make_mesh((4, 2), ("data", "model"), devices=jax.devices(),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    L, B, D = 12, 64, 128

    def f(x, ws):
        def body(c, w):
            h = jnp.tanh(c @ w)
            h = jax.lax.with_sharding_constraint(h, P("data", "model"))
            return h, None
        out, _ = jax.lax.scan(body, x, ws)
        return out.sum()

    xs = jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=NamedSharding(mesh, P("data", None)))
    ws = jax.ShapeDtypeStruct((L, D, D), jnp.float32, sharding=NamedSharding(mesh, P(None, None, "model")))
    with mesh:
        compiled = jax.jit(f).lower(xs, ws).compile()
    s = analyze(compiled.as_text())
    raw = compiled.cost_analysis()
    print(json.dumps({
        "flops": s.flops,
        "bytes": s.bytes,
        "collective_bytes": s.collective_bytes,
        "while_trips": s.while_trips,
        "raw_flops": raw["flops"],
    }))
    """
)


@pytest.fixture(scope="module")
def analysis():
    out = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_while_trip_count_detected(analysis):
    assert 12 in analysis["while_trips"].values()


def test_loop_scaled_flops_match_analytic(analysis):
    # per-device matmul flops: L * 2*B*D*D / (4 dp * 2 tp shards)
    expect = 12 * 2 * 64 * 128 * 128 / 8
    assert analysis["flops"] == pytest.approx(expect, rel=0.05)
    # and the raw XLA count must be ~L x smaller (the bug we correct)
    assert analysis["raw_flops"] < analysis["flops"] / 6


def test_collectives_scaled_by_trips(analysis):
    # one all-gather per layer inside the loop -> nonzero collective traffic
    assert analysis["collective_bytes"] > 0


def test_parser_robust_to_garbage():
    from repro.launch.hlo_analysis import analyze

    s = analyze("HloModule junk\n\nnot an hlo line at all\n")
    assert s.flops == 0.0
