"""Ahead-of-time compiles for a described TPU v5e chip at StarCoder2-3B widths.

Interpret mode (``test_kernels.py``) does not check tiling; the TPU
compiler does. These tests lower the main path's attention kernels, one
chain stage's paged decode step and the weight initializer for a v5e chip
that is described, not attached, and fail on whatever the chip's compiler
would refuse. Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import importlib.util
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch

# StarCoder2-3B attention widths; serving shapes of the continuous batcher
H, KV, HD = 24, 2, 128
PAGE, B, MAX_LEN, CHUNK = 128, 8, 2048, 128
N_PAGES = 1 + B * MAX_LEN // PAGE
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — whatever stops the description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile written to the persistent cache cannot be
    # read back without the chip: keep the cache off around these compiles
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _struct(sharding, shape, dtype=BF16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_case(name, one):
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.paged_attention import paged_chunk_attention, paged_decode_attention

    s = lambda shape, dtype=BF16: _struct(one, shape, dtype)  # noqa: E731
    pages = s((N_PAGES, KV, PAGE, HD))
    table = s((B, MAX_LEN // PAGE), jnp.int32)
    return {
        "flash_attention": (
            lambda q, k, v: flash_attention(q, k, v, causal=True),
            (s((1, 1024, H, HD)), s((1, 1024, KV, HD)), s((1, 1024, KV, HD))),
        ),
        "decode_attention": (
            decode_attention,
            (s((B, H, HD)), s((B, MAX_LEN, KV, HD)), s((B, MAX_LEN, KV, HD)), s((B,), jnp.int32)),
        ),
        "paged_decode_attention": (
            paged_decode_attention,
            (s((B, H, HD)), pages, pages, table, s((B,), jnp.int32)),
        ),
        # the chat cell's decode call: the batcher's 64 slots over a
        # 32-page table into a 128-page arena
        "paged_decode_attention.cell": (
            paged_decode_attention,
            (s((64, H, HD)), s((128, KV, PAGE, HD)), s((128, KV, PAGE, HD)),
             s((64, 32), jnp.int32), s((64,), jnp.int32)),
        ),
        # StableLM-2-1.6B widths (32 MHA heads of 64): the widest page, so
        # the tightest DMA block for the kernel's VMEM budget
        "paged_decode_attention.mha64": (
            paged_decode_attention,
            (s((64, 32, 64)), s((128, 32, PAGE, 64)), s((128, 32, PAGE, 64)),
             s((64, 32), jnp.int32), s((64,), jnp.int32)),
        ),
        "paged_chunk_attention": (
            paged_chunk_attention,
            (s((1, CHUNK, H, HD)), pages, pages, s((1, MAX_LEN // PAGE), jnp.int32),
             s((1,), jnp.int32)),
        ),
    }[name]


@pytest.mark.parametrize("name", [
    "flash_attention", "decode_attention", "paged_decode_attention", "paged_chunk_attention",
    "paged_decode_attention.cell", "paged_decode_attention.mha64",
])
def test_attention_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in


def _reader_patterns(metric):
    """The op-name patterns a roofline reader of the benchmark matches."""
    path = os.path.join(os.path.dirname(__file__), "..", "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"reader_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.KERNEL


@pytest.mark.parametrize("name,metric", [
    ("paged_decode_attention", "paged_decode_roofline"),
    ("paged_chunk_attention", "paged_chunk_roofline"),
])
def test_paged_kernel_op_carries_its_name_for_the_reader(one_chip, name, metric):
    """Called twice, unjitted, inside a program of another name, the kernel
    compiles to two custom calls named after it, and the roofline reader's
    patterns match those two instructions and nothing else."""
    jitted, args = _kernel_case(name, one_chip)
    fn = jitted.__wrapped__  # no jit scope of the kernel's name around it

    def serve_step(q, *rest):
        return fn(q, *rest), fn(q * 2, *rest)

    text = jax.jit(serve_step).lower(*args).compile().as_text()
    ops = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*)$", text, re.M))
    patterns = _reader_patterns(metric)
    matched = [op for op in ops if any(p in op for p in patterns)]
    assert len(matched) == 2, matched
    for op in matched:
        assert op.startswith(f"{name}.")
        assert 'custom_call_target="tpu_custom_call"' in ops[op]


def test_paged_decode_group_step_compiles_for_v5e(one_chip, monkeypatch):
    """One chain stage (10 of StarCoder2-3B's 30 layers) of the paged decode
    step, with the ops steered to the kernel path as on the chip."""
    from repro.kernels import ops
    from repro.models import transformer as tfm
    from repro.models.params import is_def
    from repro.serving.engine import _pick_groups

    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    cfg = get_arch("starcoder2-3b")
    per = cfg.num_layers // _pick_groups(cfg.num_layers, cfg.num_function_groups)
    params = jax.tree.map(lambda d: _struct(one_chip, d.shape, d.dtype),
                          tfm.stack_block_defs(cfg, "dense", per), is_leaf=is_def)
    arena = {kv: _struct(one_chip, (per, N_PAGES, KV, PAGE, HD)) for kv in ("k", "v")}

    def step(p, x, arena, table, cur_len):
        h, new_arena, _ = tfm.apply_stack_decode_paged(p, x, arena, table, cfg, "dense", None, cur_len)
        return h, new_arena

    compiled = jax.jit(step).lower(
        params, _struct(one_chip, (B, 1, cfg.d_model)), arena,
        _struct(one_chip, (B, MAX_LEN // PAGE), jnp.int32), _struct(one_chip, (B,), jnp.int32),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


def test_weight_init_has_no_float32_leaf_for_v5e(one_chip):
    """The largest leaf a stage draws — 10 layers of the (3072, 12288) MLP
    matrix, 755 MB in bf16 — compiles to a program whose scratch is far
    below the leaf's float32 size (3.0 GB): draw, scale and cast fuse."""
    from repro.models.layers import mlp_defs
    from repro.models.params import _draw_leaf, stack_defs

    cfg = get_arch("starcoder2-3b")
    leaf = stack_defs(mlp_defs(cfg), 10)["wi"]
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = _draw_leaf.lower(leaf, key).compile()
    mem = compiled.memory_analysis()
    n = 10 * cfg.d_model * cfg.d_ff
    assert mem.output_size_in_bytes == 2 * n
    assert mem.temp_size_in_bytes < n  # < a quarter of a float32 copy
