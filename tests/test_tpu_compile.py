"""Ahead-of-time compiles for a described TPU v5e chip at StarCoder2-3B and
Mellum2-12B-A2.5B widths.

Interpret mode (``test_kernels.py``) does not check tiling; the TPU
compiler does. These tests lower the main path's attention kernels, one
chain stage's paged decode step and the weight initializer for a v5e chip
that is described, not attached, and fail on whatever the chip's compiler
would refuse. Nothing runs, so they say nothing about results or speed.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import functools
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
# Mellum2-12B-A2.5B: 32 query heads over 4 KV heads of 128, 64 experts of
# 896 over d 2304, top 8; the repo-chat cell's 64 slots, 72-page table
# (9,216 tokens) and 240-page arena
M_H, M_KV, M_D, M_E, M_F, M_K = 32, 4, 2304, 64, 896, 8
M_SLOTS, M_TABLE, M_PAGES, M_WINDOW = 64, 72, 240, 1024


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


def _moe_rows(tokens):
    """The dropless layer's row buffer for ``tokens`` tokens: (rows, block)."""
    from repro.models.moe import block_rows

    bm = block_rows(tokens * M_K, M_E)
    return bm * (-(-tokens * M_K // bm) + M_E), bm


def _kernel_case(name, one):
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.moe_gmm import moe_gmm
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
        # Mellum2's windowed layers: the window by scalar prefetch
        "paged_decode_attention.window": (
            paged_decode_attention,
            (s((M_SLOTS, M_H, HD)), s((M_PAGES, M_KV, PAGE, HD)), s((M_PAGES, M_KV, PAGE, HD)),
             s((M_SLOTS, M_TABLE), jnp.int32), s((M_SLOTS,), jnp.int32), s((1,), jnp.int32)),
        ),
        "paged_chunk_attention.window": (
            paged_chunk_attention,
            (s((1, 512, M_H, HD)), s((M_PAGES, M_KV, PAGE, HD)), s((M_PAGES, M_KV, PAGE, HD)),
             s((1, M_TABLE), jnp.int32), s((1,), jnp.int32), s((1,), jnp.int32)),
        ),
        # the expert matmuls of a 64-slot decode step (16-row blocks) and of
        # a 4,096-token prefill chunk (128-row blocks); gate/up and down
        "moe_gmm.decode": (
            functools.partial(moe_gmm, block_rows=_moe_rows(M_SLOTS)[1]),
            (s((_moe_rows(M_SLOTS)[0], M_D)), s((M_E, M_D, M_F)), s((M_E,), jnp.int32)),
        ),
        "moe_gmm.decode_down": (
            functools.partial(moe_gmm, block_rows=_moe_rows(M_SLOTS)[1]),
            (s((_moe_rows(M_SLOTS)[0], M_F)), s((M_E, M_F, M_D)), s((M_E,), jnp.int32)),
        ),
        "moe_gmm.prefill": (
            functools.partial(moe_gmm, block_rows=_moe_rows(4096)[1]),
            (s((_moe_rows(4096)[0], M_D)), s((M_E, M_D, M_F)), s((M_E,), jnp.int32)),
        ),
    }[name]


@pytest.mark.parametrize("name", [
    "flash_attention", "decode_attention", "paged_decode_attention", "paged_chunk_attention",
    "paged_decode_attention.cell", "paged_decode_attention.mha64",
    "paged_decode_attention.window", "paged_chunk_attention.window",
])
def test_attention_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Pallas kernel is in


@pytest.mark.parametrize("name", ["moe_gmm.decode", "moe_gmm.decode_down", "moe_gmm.prefill"])
def test_moe_gmm_compiles_for_v5e(one_chip, name):
    """The grouped expert matmul at Mellum2's widths: a whole (d, f) weight
    panel per row block fits the kernel's VMEM."""
    fn, args = _kernel_case(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _unjitted(fn):
    """The kernel function without its jit scope (a partial's keywords kept)."""
    if isinstance(fn, functools.partial):
        return functools.partial(fn.func.__wrapped__, *fn.args, **fn.keywords)
    return fn.__wrapped__


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
    ("moe_gmm.decode", "moe_gmm_roofline"),
])
def test_paged_kernel_op_carries_its_name_for_the_reader(one_chip, name, metric):
    """Called twice, unjitted, inside a program of another name, the kernel
    compiles to two custom calls named after it, and the roofline reader's
    patterns match those two instructions and nothing else."""
    jitted, args = _kernel_case(name, one_chip)
    fn = _unjitted(jitted)  # no jit scope of the kernel's name around it

    def serve_step(q, *rest):
        return fn(q, *rest), fn(q * 2, *rest)

    text = jax.jit(serve_step).lower(*args).compile().as_text()
    ops = dict(re.findall(r"^\s*(?:ROOT )?%(\S+) = (.*)$", text, re.M))
    patterns = _reader_patterns(metric)
    matched = [op for op in ops if any(p in op for p in patterns)]
    assert len(matched) == 2, matched
    for op in matched:
        assert op.startswith(f"{name.split('.')[0]}.")
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


def _largest_leaf(arch):
    """The largest leaf one chain stage draws."""
    from repro.models.layers import mlp_defs
    from repro.models.moe import moe_defs
    from repro.models.params import stack_defs

    cfg = get_arch(arch)
    if arch == "starcoder2-3b":  # 10 layers of the (3072, 12288) MLP matrix
        return stack_defs(mlp_defs(cfg), 10)["wi"]
    return stack_defs(moe_defs(cfg), 4)["wi_gate"]  # 4 layers x 64 x (2304, 896)


@pytest.mark.parametrize("arch", ["starcoder2-3b", "mellum2-12b-a2.5b"])
def test_weight_init_has_no_float32_leaf_for_v5e(one_chip, arch):
    """The largest leaf a stage draws — StarCoder2-3B's MLP matrix over 10
    layers, 755 MB in bf16, or Mellum2's experts' gate matrices over 4
    layers, 1.06 GB — compiles to a program whose scratch is far below the
    leaf's float32 size: draw, scale and cast fuse."""
    from repro.models.params import _draw_leaf

    leaf = _largest_leaf(arch)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    compiled = _draw_leaf.lower(leaf, key).compile()
    mem = compiled.memory_analysis()
    n = 1
    for dim in leaf.shape:
        n *= dim
    assert mem.output_size_in_bytes == 2 * n
    assert mem.temp_size_in_bytes < n  # < a quarter of a float32 copy


@pytest.mark.parametrize("step", ["decode", "prefill_chunk"])
def test_mellum2_stage_steps_compile_for_v5e(one_chip, monkeypatch, step):
    """One chain stage of Mellum2 (4 layers: 3 windowed, 1 full with YaRN,
    every one with 64 experts) against the cell's 240-page arena, with the
    ops steered to the kernels as on the chip: the decode step of 64 slots
    and a 4,096-token prefill chunk. The decode step reads each layer's
    experts in place from the stage's stacked matrices: no instruction
    outputs a layer's (64, d, f) expert bank, and its scratch stays small."""
    import dataclasses

    from repro.kernels import ops
    from repro.models import transformer as tfm
    from repro.models.params import is_def

    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    cfg = dataclasses.replace(get_arch("mellum2-12b-a2.5b"), num_layers=8, num_function_groups=2)
    per = 4
    params = jax.tree.map(lambda d: _struct(one_chip, d.shape, d.dtype),
                          tfm.stack_block_defs(cfg, "moe", per), is_leaf=is_def)
    arena = {kv: _struct(one_chip, (per, M_PAGES, M_KV, PAGE, HD)) for kv in ("k", "v")}
    table = lambda b: _struct(one_chip, (b, M_TABLE), jnp.int32)  # noqa: E731
    i32 = lambda n: _struct(one_chip, (n,), jnp.int32)  # noqa: E731
    if step == "decode":
        def fn(p, x, arena, table, cur_len):
            return tfm.apply_stack_decode_paged(p, x, arena, table, cfg, "moe", None, cur_len,
                                                True, per)
        args = (params, _struct(one_chip, (M_SLOTS, 1, M_D)), arena, table(M_SLOTS), i32(M_SLOTS))
    else:
        def fn(p, x, arena, table, start, valid):
            return tfm.apply_stack_prefill_chunk_paged(p, x, arena, table, cfg, "moe", None,
                                                       start, valid, 0)
        args = (params, _struct(one_chip, (1, 4096, M_D)), arena, table(1), i32(1), i32(1))
    compiled = jax.jit(fn, donate_argnums=(2,)).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "moe_gmm" in text
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 2**30
    if step == "decode":
        banks = re.findall(rf"^\s*(?:ROOT )?%\S+ = bf16\[(?:{M_E},{M_D},{M_F}|{M_E},{M_F},{M_D})\]",
                           text, re.M)
        assert not banks, banks
        assert temp < 128 << 20
