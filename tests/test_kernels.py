"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import moe_gmm
from repro.kernels.ssd_scan import ssd_scan

RNG = jax.random.PRNGKey(7)


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,t,h,kv,hd", [
    (2, 256, 4, 2, 64),
    (1, 128, 8, 8, 32),
    (2, 256, 4, 1, 64),   # MQA
    (1, 512, 2, 2, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_vs_ref(b, t, h, kv, hd, causal, dtype):
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, t, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64, interpret=True)
    expect = ref.mha_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("b,s,h,kv,hd", [(2, 512, 4, 2, 64), (3, 1024, 8, 1, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_vs_ref(b, s, h, kv, hd, dtype):
    ks = jax.random.split(RNG, 4)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32).astype(dtype)
    cur = jax.random.randint(ks[3], (b,), 1, s, jnp.int32)
    out = decode_attention(q, k, v, cur, block_k=256, interpret=True)
    expect = ref.decode_attn_ref(q, k, v, cur)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


@pytest.mark.parametrize("b,pages,page,h,kv,hd,width,ppb,lengths", [
    pytest.param(3, 9, 128, 4, 2, 64, 3, None, None, id="3-9-128-4-2-64"),
    pytest.param(2, 5, 256, 8, 1, 32, 3, None, None, id="2-5-256-8-1-32"),  # MQA
    # four 32-key blocks, the last partial; a one-page and a one-block sequence
    pytest.param(3, 17, 16, 4, 2, 64, 8, 2, (117, 45, 3), id="multi-block-partial-last"),
    # a length on a page boundary (3 pages) and one on a block boundary (2 blocks)
    pytest.param(2, 17, 16, 4, 2, 64, 8, 2, (48, 64), id="page-and-block-boundary"),
    pytest.param(1, 9, 128, 6, 2, 128, 4, None, (300,), id="one-slot-frozen-step"),
    pytest.param(2, 9, 16, 4, 4, 32, 4, 2, (50, 17), id="mha"),  # G = 1
    # 16-token pages, a 96-page table: 48 pages (768 keys) per block
    pytest.param(2, 97, 16, 4, 2, 64, 96, None, (1400, 17), id="page16-wide-table"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention_vs_contiguous(b, pages, page, h, kv, hd, width, ppb, lengths, dtype):
    """Block-table-indirect kernel == gather-to-contiguous + dense oracle,
    over one or several DMA blocks of ``ppb`` pages (derived when None)."""
    from repro.kernels.paged_attention import gather_pages, paged_decode_attention

    ks = jax.random.split(RNG, 5)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    # ragged sequences through a shuffled table; padded rows hit page 0
    bt = jax.random.randint(ks[3], (b, width), 1, pages, jnp.int32)
    if lengths is None:
        cur = jax.random.randint(ks[4], (b,), 1, width * page, jnp.int32)
    else:
        cur = jnp.array(lengths, jnp.int32)
    out = paged_decode_attention(q, kp, vp, bt, cur, interpret=True, pages_per_block=ppb)
    expect = ref.decode_attn_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), cur)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


def test_paged_decode_attention_scratch_rows_masked():
    """Masked slots (all-scratch rows, cur_len 0) must produce EXACT zeros
    — not a mean of scratch-page garbage — and not disturb live rows: an
    8-slot batch with 2 live slots, as the batcher sends its capacity."""
    from repro.kernels.paged_attention import gather_pages, paged_decode_attention

    ks = jax.random.split(RNG, 3)
    b, pages, page, h, kv, hd = 8, 5, 128, 2, 2, 32
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32)
    live = np.array([2, 5])
    bt = jnp.zeros((b, 2), jnp.int32).at[live].set(jnp.array([[1, 2], [4, 3]], jnp.int32))
    cur = jnp.zeros((b,), jnp.int32).at[live].set(jnp.array([page + 7, 2 * page], jnp.int32))
    out = paged_decode_attention(q, kp, vp, bt, cur, interpret=True)
    empty = np.setdiff1d(np.arange(b), live)
    np.testing.assert_array_equal(np.asarray(out)[empty], 0.0)
    expect = ref.decode_attn_ref(
        q[live], gather_pages(kp, bt[live]), gather_pages(vp, bt[live]), cur[live]
    )
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("c,start,page,h,kv,hd,block_q", [
    (16, 0, 128, 4, 2, 64, 128),     # first chunk of a prompt
    (32, 200, 128, 4, 2, 64, 16),    # later chunk, two q blocks, crosses a page
    (8, 37, 16, 2, 1, 32, 128),      # MQA, small pages, unaligned start
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_chunk_attention_vs_contiguous(c, start, page, h, kv, hd, block_q, dtype):
    """Chunked-prefill kernel == gather-to-contiguous + causal oracle with
    the chunk's absolute offset."""
    from repro.kernels.paged_attention import gather_pages, paged_chunk_attention

    ks = jax.random.split(RNG, 4)
    pages, width = 9, 4
    q = jax.random.normal(ks[0], (1, c, h, hd), jnp.float32).astype(dtype)
    kp = jax.random.normal(ks[1], (pages, kv, page, hd), jnp.float32).astype(dtype)
    vp = jax.random.normal(ks[2], (pages, kv, page, hd), jnp.float32).astype(dtype)
    bt = jax.random.permutation(ks[3], jnp.arange(1, pages, dtype=jnp.int32))[None, :width]
    out = paged_chunk_attention(q, kp, vp, bt, jnp.array([start], jnp.int32),
                                block_q=block_q, interpret=True)
    expect = ref.mha_ref(q, gather_pages(kp, bt), gather_pages(vp, bt), causal=True,
                         q_offset=start)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))


def test_decode_attention_length_edge_cases():
    b, s, h, kv, hd = 2, 256, 2, 2, 32
    ks = jax.random.split(RNG, 3)
    q = jax.random.normal(ks[0], (b, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, hd), jnp.float32)
    for cur in (jnp.array([1, s]), jnp.array([s, 1])):
        out = decode_attention(q, k, v, cur, block_k=128, interpret=True)
        expect = ref.decode_attn_ref(q, k, v, cur)
        np.testing.assert_allclose(np.asarray(out), np.asarray(expect), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("prompt,needle", [
    (200, r"seq lengths \(200,200\) must divide blocks \(128,128\)"),  # dense prefill
    (256, None),                                                      # aligned: the kernel
])
def test_kernel_mode_dense_prefill_raises_instead_of_reference(monkeypatch, prompt, needle):
    """On a TPU every attention op runs its kernel: a dense prefill whose
    length the flash blocks do not divide raises with the shapes from the
    kernel wrapper, it never returns the jnp reference's answer."""
    from repro.analysis.dispatch import TRACER
    from repro.configs import get_arch, reduced_config
    from repro.kernels import ops
    from repro.models.model import build_model

    model = build_model(reduced_config(get_arch("starcoder2-3b")))
    params = jax.eval_shape(model.init, RNG)
    batch = {"tokens": jax.ShapeDtypeStruct((1, prompt), jnp.int32)}
    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    TRACER.arm()
    try:
        before = TRACER.path_counts()
        if needle is None:
            # aligned shapes trace into the flash kernel (lowering it needs a TPU)
            logits, _ = jax.eval_shape(model.prefill_fn, params, batch)
            assert logits.shape == (1, model.cfg.vocab_size)
        else:
            with pytest.raises(ValueError, match=needle):
                jax.eval_shape(model.prefill_fn, params, batch)
        after = TRACER.path_counts()
    finally:
        TRACER.disarm()
    # the op entered its kernel path, never the reference
    assert after.get(("attention", "kernel"), 0) > before.get(("attention", "kernel"), 0)
    assert after.get(("attention", "ref"), 0) == before.get(("attention", "ref"), 0)


def test_kernel_mode_dense_decode_raises_instead_of_reference(monkeypatch):
    """Same rule for the dense decode kernel: a cache width its 512-row kv
    blocks do not divide raises with the shapes."""
    from repro.kernels import ops
    from repro.models import attention as attn_mod

    monkeypatch.setattr(ops, "_mode", lambda: "kernel")
    q = jax.ShapeDtypeStruct((2, 1, 4, 128), jnp.bfloat16)
    cache = jax.ShapeDtypeStruct((2, 768, 2, 128), jnp.bfloat16)
    cur = jax.ShapeDtypeStruct((2,), jnp.int32)
    with pytest.raises(ValueError, match="S=768 must divide block_k=512"):
        jax.eval_shape(attn_mod.decode_attention, q, cache, cache, cur)


@pytest.mark.parametrize("b,t,h,grp,p,n,chunk", [
    (2, 128, 4, 1, 32, 16, 32),
    (1, 256, 2, 2, 64, 32, 64),
    (1, 64, 2, 1, 16, 8, 64),  # single chunk
])
def test_ssd_scan_vs_ref(b, t, h, grp, p, n, chunk):
    ks = jax.random.split(RNG, 5)
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, t, grp, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (b, t, grp, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    a_log = jax.random.normal(ks[4], (h,), jnp.float32) * 0.3
    d_skip = jnp.ones((h,), jnp.float32)
    out = ssd_scan(x, bm, cm, dt, a_log, d_skip, chunk=chunk, interpret=True)
    expect, _ = ref.ssd_ref(x, bm, cm, dt, a_log, d_skip)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expect, np.float32), rtol=5e-4, atol=5e-4)


def test_models_ssd_chunked_matches_oracle():
    """The model's jnp chunked SSD (the lowering path) == naive O(T^2) oracle."""
    from repro.models.ssm import ssd_chunked

    ks = jax.random.split(RNG, 5)
    b, t, h, grp, p, n = 2, 96, 4, 2, 16, 8
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32)
    bm = jax.random.normal(ks[1], (b, t, grp, n), jnp.float32) * 0.5
    cm = jax.random.normal(ks[2], (b, t, grp, n), jnp.float32) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[3], (b, t, h), jnp.float32))
    a_log = jax.random.normal(ks[4], (h,), jnp.float32) * 0.3
    d_skip = jnp.ones((h,), jnp.float32)
    y, state = ssd_chunked(x, bm, cm, dt, a_log, d_skip, chunk=32)
    y_ref, state_ref = ref.ssd_ref(x, bm, cm, dt, a_log, d_skip)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref, np.float32), rtol=5e-4, atol=5e-4)
    np.testing.assert_allclose(np.asarray(state), np.asarray(state_ref), rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("e,c,d,f", [(4, 64, 128, 256), (2, 128, 64, 128), (1, 32, 32, 32)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_vs_ref(e, c, d, f, dtype):
    ks = jax.random.split(RNG, 2)
    xe = jax.random.normal(ks[0], (e, c, d), jnp.float32).astype(dtype)
    w = (jax.random.normal(ks[1], (e, d, f), jnp.float32) * 0.05).astype(dtype)
    out = moe_gmm(xe, w, block_c=32, block_f=32, block_d=32, interpret=True)
    expect = ref.gmm_ref(xe, w)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(expect, np.float32), **tol(dtype))
