"""The float32 reference that ``tiny.json`` names: its weights are the
deployed ones, bit for bit, and the program's served tokens sit on its best
logits; the shared attention and its window."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_common as rc
import run

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def tiny():
    with open(os.path.join(DATA, "tiny.json")) as f:
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
def system():
    cfg = tiny()
    s = run.System(cfg, seed=2**33 + 5)
    yield cfg, s
    s.shutdown(timeout=30)


def test_reference_draws_the_deployed_weights_bit_for_bit(system):
    cfg, s = system
    m, ws = cfg["model"], run.weight_seed(2**33 + 5)
    reference = run.model_modules(cfg)[0]
    names = s.engine.chain_names()
    head = flat(s.platform.spec_of(names[-1]).params)
    shared = reference.shared_weights(m, ws)
    assert sorted(head) == sorted(shared)
    for k in shared:
        np.testing.assert_array_equal(np.asarray(head[k]), np.asarray(shared[k]), err_msg=k)
    for i, name in enumerate(names[1:-1]):
        got = flat(s.platform.spec_of(name).params)
        want = reference.stage_weights(m, ws, i)
        assert sorted(got) == sorted(want)
        for k in want:
            assert got[k].dtype == want[k].dtype
            np.testing.assert_array_equal(np.asarray(got[k]), np.asarray(want[k]), err_msg=k)


def test_served_tokens_sit_on_the_references_best_logits(system):
    cfg, s = system
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["model"]["vocab_size"], (n,), dtype=np.int32) for n in (9, 40)]
    served = []
    for p in prompts:
        out = s.batcher.submit({"tokens": jax.numpy.asarray(p[None, :])}, 12).result(timeout=120)
        served.append((p, np.asarray(out["tokens"][0], np.int32)))
    reference = run.model_modules(cfg)[0]
    gaps = reference.served_gaps(cfg["model"], run.weight_seed(2**33 + 5), served)
    assert [len(g) for g in gaps] == [12, 12]
    # bf16 program against the float32 reference at toy width: the served
    # token is the reference's best or within bf16 rounding of it
    assert max(float(np.max(g)) for g in gaps) < cfg["limits"]["max_logit_gap"]
    assert min(float(np.min(g)) for g in gaps) >= 0.0


def _qkv(t, h=4, kv=2, hd=8):
    keys = jax.random.split(jax.random.PRNGKey(3), 3)
    return (jax.random.normal(keys[0], (t, h, hd)), jax.random.normal(keys[1], (t, kv, hd)),
            jax.random.normal(keys[2], (t, kv, hd)))


def test_a_window_as_long_as_the_sequence_is_causal_attention():
    q, k, v = _qkv(2 * rc.Q_BLOCK)
    with jax.default_matmul_precision("highest"):
        full = rc.attention(q, k, v, False)
        np.testing.assert_array_equal(rc.attention(q, k, v, False, window=2 * rc.Q_BLOCK), full)
        np.testing.assert_array_equal(rc.attention(q, k, v, False, window=jnp.int32(5000)), full)


def test_a_window_of_one_key_returns_each_rows_own_value():
    q, k, v = _qkv(rc.Q_BLOCK)
    with jax.default_matmul_precision("highest"):
        got = rc.attention(q, k, v, False, window=1)
    # query head j reads KV head j // 2
    np.testing.assert_allclose(got, jnp.repeat(v, 2, axis=1), rtol=1e-6)
